"""The comparison that decides ``correct`` comes out false when the timed
path is broken underneath: an answer altered where it is produced, half
of a batch left out, a step that returns its input unchanged.  (No cell
spans chips, so there is no exchange between chips to leave out.)"""
import numpy as np
import pytest

from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench_faults")))


@pytest.fixture(autouse=True)
def fresh_program_caches():
    """Each run is a process of its own: no test may find the results a
    broken program cached in an earlier one."""
    from repro.core import plan

    plan.clear_caches()
    yield
    plan.clear_caches()


def _po_rows(net):
    return [s for bus in net.pos.values() for s in bus]


def _eval_fault(kind):
    from repro.core import flow

    real = flow.evaluate_suite

    def broken(nets, lanes, n_words, **kw):
        outs, stats = real(nets, lanes, n_words, **kw)
        outs = [np.array(v) for v in outs]
        if kind == "altered":        # one output of one circuit flipped
            outs[0][_po_rows(nets[0])[0]] ^= 1
        elif kind == "half":         # half of the circuits left out
            for v in outs[len(outs) // 2:]:
                v[:] = 0
        elif kind == "unchanged":    # the value buffer comes back as sent
            outs = []
            for net, ln in zip(nets, lanes):
                v = np.zeros((net.n_signals, n_words), dtype=np.uint32)
                for s, w in ln.items():
                    v[s] = w
                outs.append(v)
        return outs, stats

    return broken


@pytest.mark.parametrize("kind", ["altered", "half", "unchanged"])
def test_eval_fault_is_not_correct(root, monkeypatch, kind):
    from repro.core import flow

    monkeypatch.setattr(flow, "evaluate_suite", _eval_fault(kind))
    res = tiny.run(root, "tiny.eval")
    assert not res["correct"]
    assert res["checks"]["po_word_mismatches"]["value"] > 0


@pytest.mark.parametrize("kind", ["altered", "half"])
def test_sweep_fault_is_not_correct(root, monkeypatch, kind):
    from repro.core import timing_vec

    real = timing_vec.SuiteTimingProgram.run

    def broken(self, tables):
        cps = real(self, tables)
        if kind == "altered":        # one critical path off by 1 cps
            cps[0, 0] += 1
        else:                        # half the circuits' rows left out
            cps[len(cps) // 2:] = 0
        return cps

    monkeypatch.setattr(timing_vec.SuiteTimingProgram, "run", broken)
    res = tiny.run(root, "tiny.sweep", seed=5)
    assert not res["correct"]
    assert res["checks"]["record_mismatches"]["value"] > 0
