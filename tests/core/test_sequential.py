"""Registers as a first-class part of the flow: the netlist's register
primitive, cycle simulation on the device (``flow.evaluate_sequential``)
against the plain stepping and the systolic array's register-transfer
model, packing into flip-flop slots, timing with register-to-register
paths, and register correspondence in the equivalence checker.  Netlists
without registers keep every digest and record they had."""
from collections import Counter

import numpy as np
import pytest

from bench.reference import systolic as ref_sys
from repro.core import flow
from repro.core.alm import ARCHS, FF_PER_ALM
from repro.core.circuits import systolic_ws
from repro.core.netlist import (TT_AND2, TT_MUX, TT_XOR2, Netlist,
                                eval_sequential)

#: content and pack digests of the 17 suite circuits, and three circuits'
#: records under the three canonical architectures (pack seed 0), as the
#: flow gave them before registers existed
SUITE_DIGESTS = {
    "conv1d-fu": ("d005068319a50a10ba3981734f28ed7f",
                  "f8d9a91d07dfbd39ce7b1445d902a2f1"),
    "conv1d-pw-fu": ("2a9aeada3417f3538f5ca60ad254bbeb",
                     "8381c404c418d8de1aa770c3cbc5b7fb"),
    "conv2d-fu": ("824b9dc915b256acba715c1c04bf9916",
                  "b1a88eae725029912974842c8538ab5b"),
    "gemms-fu": ("8f51bb959640e9607966ed5818f61dea",
                 "c018dcc69b25507efc9ea9e368fd2696"),
    "gemmt-fu": ("77a6ed6691feeabc1570811cbb316e0b",
                 "ee34278afd6894231605ebc67759577c"),
    "fc-fu": ("60bc561c55543c7f647a9207d6a02281",
              "70a541c41d29f0b5495f0927d91c6513"),
    "gemm-dense-fu": ("104a8aa785a9906f92bd733e361487e7",
                      "c0e4e1cc39cc20261f0e19495127d4ac"),
    "dla-like": ("9ca5056510414e8d14642ec7e465d181",
                 "0691751726de6bece058b2800abd1256"),
    "tpu-like": ("f54fdf052e4f75c5d133b1cedd4d4037",
                 "7a97936f2dade7e049a70614bbc9be3d"),
    "dnnweaver-like": ("32d095df8d72940f185879fbdd6ea8b7",
                       "e79ef0d22fc3e191f7088bd512401747"),
    "conv-like": ("291b03b13245e09915b6cb5f0909c5f6",
                  "a94481cf861faa75b6076e4704bf1ee9"),
    "lstm-like": ("6c16443602544fb306ba62bc83585186",
                  "66eead71a0426a7f4da68b6a3e2460db"),
    "or1200-like": ("6330acba983a4a482ffa336a9e11b158",
                    "fc52d8cad97469d84dc2c3d111b22d22"),
    "blob-merge-like": ("0729f1cdb1e8637eae250affba98cc85",
                        "855c4c9bab336437a12d8b91f272e730"),
    "arm-core-like": ("e574f68ac7908ca64ca1921a00154ec2",
                      "06d589996dfa6b55a352b8222f3f1c69"),
    "sha-like": ("53f349db097440c01cfc9379bcc793bf",
                 "c125306ff6b230530a3a26b30de90d1a"),
    "stereovision-like": ("a5c4836ad15fe6aba9192bebbb755060",
                          "41bd4cc50d9e93f504c166a36f1e8cb1"),
}
RECORDS = {
    ("fc-fu", "baseline"): (714, 72, 5320728.0, 8252.09, 43907126321.520004,
                            0),
    ("fc-fu", "dd5"): (595, 60, 4598882.567999999, 7680.32,
                       35320889764.66175, 229),
    ("fc-fu", "dd6"): (595, 60, 4624599.42, 8220.32, 38015687104.2144, 229),
    ("dla-like", "baseline"): (207, 21, 1542564.0, 7619.48,
                               11753535546.72, 0),
    ("dla-like", "dd5"): (166, 17, 1283049.5903999999, 7378.72,
                          9467263673.676287, 76),
    ("dla-like", "dd6"): (165, 17, 1282451.94, 7858.72, 10078430709.9168,
                          78),
    ("or1200-like", "baseline"): (137, 14, 1020924.0, 11084.72,
                                  11316656681.279999, 0),
    ("or1200-like", "dd5"): (118, 12, 912047.2991999999, 11153.52,
                             10172537792.573183, 38),
    ("or1200-like", "dd6"): (118, 12, 917147.448, 11413.52,
                             10467880740.69696, 38),
}
RECORD_KEYS = ("alms", "lbs", "area_mwta", "critical_path_ps", "adp",
               "concurrent_luts")


@pytest.fixture(scope="module")
def suite():
    from repro.core.circuits import koios_suite, kratos_suite, vtr_suite

    return {n.name: n for n in kratos_suite() + koios_suite() + vtr_suite()}


@pytest.fixture(scope="module")
def arrays():
    return {n: systolic_ws(f"sa{n}", rows=n, cols=n) for n in (2, 4)}


def _counter() -> Netlist:
    """A 2-bit counter with enable: c <- c + en."""
    net = Netlist("counter")
    en = net.add_pi_bus("en", 1)[0]
    c = net.add_reg_bus("c", 2)
    net.connect_reg(c[0], net.add_lut((c[0], en), TT_XOR2))
    carry = net.add_lut((c[0], en), TT_AND2)
    net.connect_reg(c[1], net.add_lut((c[1], carry), TT_XOR2))
    net.set_po_bus("c", c)
    return net


# ---------------------------------------------------------------------------
# the register primitive
# ---------------------------------------------------------------------------


def test_counter_steps_by_hand():
    net = _counter()
    en = [1, 1, 0, 1, 1, 1]          # one vector
    out = eval_sequential(net, {net.pis[0]: en}, len(en), 1)
    c = net.reg_buses["c"]
    assert [o[c[0]] | o[c[1]] << 1 for o in out] == [0, 1, 2, 2, 3, 0]


def test_register_api_refuses_misuse():
    net = _counter()
    with pytest.raises(ValueError):
        net.connect_reg(net.reg_buses["c"][0], net.pis[0])   # connected
    with pytest.raises(ValueError):
        net.connect_reg(net.pis[0], net.pis[0])              # not a Q
    open_reg = Netlist("open")
    open_reg.add_reg_bus("r", 1)
    with pytest.raises(ValueError):
        eval_sequential(open_reg, {}, 1)


def test_sources_are_the_pis_then_every_q():
    net = _counter()
    assert net.sources == net.pis + net.reg_buses["c"]
    comb = Netlist("comb")
    comb.add_pi_bus("a", 2)
    assert comb.sources is comb.pis


@pytest.mark.parametrize("name", sorted(SUITE_DIGESTS))
def test_combinational_digests_are_unchanged(suite, name):
    net = suite[name]
    assert not net.regs
    assert (net.content_digest(), net.pack_digest()) == SUITE_DIGESTS[name]


@pytest.mark.parametrize("name,arch", sorted(RECORDS))
def test_combinational_records_are_unchanged(suite, name, arch):
    rec = flow.pack_and_analyze(suite[name], ARCHS[arch], seeds=(0,))
    assert tuple(rec[k] for k in RECORD_KEYS) == RECORDS[(name, arch)]


def test_digests_see_the_registers():
    a, b = _counter(), _counter()
    assert a.content_digest() == b.content_digest()
    (d0, q0), (d1, q1) = b.regs
    b.regs[0], b.regs[1] = (d1, q0), (d0, q1)
    assert a.content_digest() != b.content_digest()
    assert a.pack_digest() != b.pack_digest()


def test_sweep_and_techmap_keep_the_logic_in_front_of_d():
    from repro.core.techmap import techmap

    net = Netlist("mux")
    s, a, b = net.add_pi_bus("in", 3)
    r = net.add_reg_bus("r", 1)[0]
    net.connect_reg(r, net.add_lut((s, a, b), TT_MUX))
    net.add_lut((a, b), TT_AND2)          # dead: feeds nothing
    net.set_po_bus("r", [r])
    out = techmap(net.sweep())
    assert out.n_luts == 1 and out.regs == net.regs
    assert out.reg_buses == {"r": [r]} and out.driver[r] == ("reg", 0)
    assert out.content_digest() != techmap(_counter().sweep()).content_digest()


def test_systolic_cell_sizes(arrays):
    """One 8x8 signed multiplier and a 32-bit accumulate per cell below
    the first row; 57 registers a cell."""
    net = arrays[4]
    assert len(net.regs) == 16 * 57
    assert net.n_luts == 16 * 138
    assert net.n_adders == 16 * 12 + 12 * 32
    assert len(net.pis) == 4 * 8 + 4 * 8 + 4 + 4
    assert list(net.pos) == [f"y{j}" for j in range(4)]


# ---------------------------------------------------------------------------
# cycle simulation
# ---------------------------------------------------------------------------


def _schedule(net, n_rows, n_tiles, n_words, seed):
    """Random weights and activations on the systolic schedule: the PI
    stream in ``net.pis`` order and the register-transfer model's
    output words."""
    R = C = n_rows
    M = 2 * R
    B = 32 * n_words
    rng = np.random.default_rng(seed)
    W = rng.integers(-128, 128, (n_tiles, B, R, C), dtype=np.int8)
    X = rng.integers(-128, 128, (n_tiles, B, M, R), dtype=np.int8)
    st = ref_sys.streams(W, X)
    T = st["x"].shape[0]
    slot = {s: i for i, s in enumerate(net.pis)}
    ones = np.uint32(0xFFFFFFFF)
    stream = np.zeros((T, len(net.pis), n_words), dtype=np.uint32)

    def put(bus, words):
        stream[:, [slot[s] for s in bus], :] = words

    for i in range(R):
        put(net.pi_buses[f"x{i}"], ref_sys.pack_bits(st["x"][:, :, i], 8))
        stream[st["wswap"][:, i], slot[net.pi_buses["wswap"][i]]] = ones
    for j in range(C):
        put(net.pi_buses[f"w{j}"], ref_sys.pack_bits(st["w"][:, :, j], 8))
        stream[st["wshift"][:, j], slot[net.pi_buses["wshift"][j]]] = ones
    y = ref_sys.simulate(st)
    want = np.concatenate([ref_sys.pack_bits(y[:, :, j], 32)
                           for j in range(C)], axis=1)
    return stream, want


@pytest.mark.parametrize("n,use_pallas", [(2, True), (2, False),
                                          (4, True), (4, False)])
def test_evaluate_sequential_equals_both_references(arrays, n, use_pallas):
    """Load -> stream -> swap -> drain over three tiles: every output bit
    of every cycle equals the register-transfer model and the plain
    stepping of the netlist."""
    net = arrays[n]
    stream, want = _schedule(net, n, 3, 2, seed=n)
    T = stream.shape[0]
    outs, stats = flow.evaluate_sequential([net], [stream],
                                           use_pallas=use_pallas)
    assert outs[0].shape == want.shape
    assert np.array_equal(outs[0], want)
    assert stats["padded_lut_rows"] >= stats["real_lut_rows"] == net.n_luts
    if n == 2:
        pis = {s: [int(stream[t, i, 0]) | int(stream[t, i, 1]) << 32
                   for t in range(T)] for i, s in enumerate(net.pis)}
        ref = eval_sequential(net, pis, T, 64)
        po = [s for bus in net.pos.values() for s in bus]
        got = np.array([[ref[t][s] for s in po] for t in range(T)],
                       dtype=np.uint64)
        words = outs[0].astype(np.uint64)
        assert np.array_equal(got, words[:, :, 0] | words[:, :, 1] << 32)


@pytest.mark.parametrize("rows,cols", [(2, 3), (3, 2)])
def test_array_variants_simulate_like_the_plain_stepping(rows, cols):
    """Arrays that are not square: the device scan equals the plain
    stepping on random streams (every input bit random in every cycle)."""
    net = systolic_ws("v", rows=rows, cols=cols)
    assert len(net.regs) == rows * cols * 57
    assert list(net.pos) == [f"y{j}" for j in range(cols)]
    T = 9
    stream = np.random.default_rng(rows * 10 + cols).integers(
        0, 2**32, (T, len(net.pis), 1), dtype=np.uint32)
    outs, _ = flow.evaluate_sequential([net], [stream], use_pallas=False)
    ref = eval_sequential(net, {s: [int(stream[t, i, 0]) for t in range(T)]
                                for i, s in enumerate(net.pis)}, T, 32)
    po = [s for bus in net.pos.values() for s in bus]
    assert np.array_equal(outs[0][:, :, 0], np.array(
        [[ref[t][s] for s in po] for t in range(T)], dtype=np.uint32))


def _shift_register() -> Netlist:
    """Registers fed straight by a primary input and by another Q, with
    no logic at all: an empty plan."""
    net = Netlist("shift")
    x = net.add_pi_bus("x", 2)
    r = net.add_reg_bus("r", 3)
    net.connect_reg(r[0], x[0])
    net.connect_reg(r[1], r[0])
    net.connect_reg(r[2], x[1])
    net.set_po_bus("y", [r[1], r[2], x[0]])
    return net


def _chains(want_cout: bool) -> Netlist:
    """An accumulator through a chain, with or without its carry out,
    and a level holding both LUTs and a chain that reads a LUT output."""
    net = Netlist("acc")
    x = net.add_pi_bus("x", 3)
    acc = net.add_reg_bus("acc", 3)
    s, co = net.add_chain(acc, x, want_cout=want_cout)
    for q, d in zip(acc, s):
        net.connect_reg(q, d)
    t = net.add_lut((x[0], x[1]), TT_XOR2)           # level 1
    u = net.add_lut((x[1], acc[2]), TT_AND2)         # level 1
    v = net.add_lut((t, u, x[2]), TT_MUX)            # level 2
    s2, co2 = net.add_chain([t, u], [x[2], acc[0]])  # level 2, reads t, u
    net.set_po_bus("y", s + s2 + [v] + ([co] if want_cout else [co2 or 0]))
    return net


def _undriven() -> Netlist:
    """Signals nothing drives, read by a LUT, a chain, a D and a PO."""
    net = Netlist("undriven")
    x = net.add_pi_bus("x", 2)
    r = net.add_reg_bus("r", 2)
    u, w = net.new_sig(), net.new_sig()
    a = net.add_lut((u, x[0], r[1]), TT_MUX)
    s, _ = net.add_chain([a, w], [x[1], r[0]], cin=u)
    net.connect_reg(r[0], s[1])
    net.connect_reg(r[1], w)
    net.set_po_bus("y", s + [a, u, r[1]])
    return net


SMALL_NETS = {"shift": _shift_register, "chain-cout": lambda: _chains(True),
              "chain-no-cout": lambda: _chains(False),
              "undriven": _undriven}


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("kind", sorted(SMALL_NETS))
def test_small_registered_netlists_simulate_like_the_plain_stepping(
        kind, use_pallas):
    """Edge cases of the cycle program's slot plan against the plain
    stepping on random streams; an undriven signal reads 0 in both."""
    net = SMALL_NETS[kind]()
    T, N = 7, 2
    stream = np.random.default_rng(len(kind)).integers(
        0, 2**32, (T, len(net.pis), N), dtype=np.uint32)
    outs, _ = flow.evaluate_sequential([net], [stream],
                                       use_pallas=use_pallas)
    driven = set(net.sources) | set(net.lut_out) | {
        s for ch in net.chains for s in ch.sums + [ch.cout]}
    zeros = {s: [0] * T for s in range(2, net.n_signals) if s not in driven}
    assert (kind == "undriven") == bool(zeros)
    pis = {s: [int(stream[t, i, 0]) | int(stream[t, i, 1]) << 32
               for t in range(T)] for i, s in enumerate(net.pis)}
    ref = eval_sequential(net, {**pis, **zeros}, T, 64)
    po = [s for bus in net.pos.values() for s in bus]
    got = np.array([[ref[t][s] for s in po] for t in range(T)],
                   dtype=np.uint64)
    words = outs[0].astype(np.uint64)
    assert np.array_equal(got, words[:, :, 0] | words[:, :, 1] << 32)


def _blocks(plan):
    """Each padded level's ``(start, end)`` rows of its block."""
    out = []
    for (hl, hc), (base, ins, *_rest, a, _b, _cin, _last) in zip(
            plan.flags, plan.buckets):
        size = (ins.shape[1] if hl else 0) + (
            a.shape[1] * (a.shape[2] + 1) if hc else 0)
        out += [(int(b), int(b) + size) for b in base]
    return out


@pytest.mark.parametrize("kind", ["sa4"] + sorted(SMALL_NETS))
def test_slot_layout_is_sound(arrays, kind):
    """Sources sit contiguously at row 2; the level blocks follow without
    overlap; every signal a level, a D or a PO reads has one row of its
    own (or ``CONST0`` where nothing drives it), inside the block of the
    level that writes it; D and PO rows map through the same slots."""
    from repro.core.eval_jax import plan_netlist, seq_program

    net = arrays[4] if kind == "sa4" else SMALL_NETS[kind]()
    prog = seq_program(net)
    plan, fused = prog.plan, plan_netlist(net)
    S = len(net.sources)
    assert np.array_equal(plan.slot[net.sources], 2 + np.arange(S))
    assert list(plan.slot[:2]) == [0, 1]
    blocks = _blocks(plan)
    ends = [2 + S] + [e for _, e in blocks]
    assert all(s == e for (s, _), e in zip(blocks, ends))
    assert ends[-1] == plan.n_rows
    # every written row lies in its level's block, one signal a row
    written = {}
    for bk, (base, *_r) in zip(fused.buckets, plan.buckets):
        for r, b in enumerate(base):
            lo, hi = next(blk for blk in blocks if blk[0] == b)
            outs = [bk.lut_out[r], bk.ch_sums[r].ravel(), bk.ch_cout[r]]
            for s in np.concatenate(outs):
                if s != fused.sink:
                    assert lo <= plan.slot[s] < hi
                    written[int(s)] = int(plan.slot[s])
    rows = list(written.values()) + list(plan.slot[net.sources])
    assert len(set(rows)) == len(rows)
    reads = {int(s) for bk in fused.buckets
             for arr in (bk.lut_ins, bk.ch_a, bk.ch_b, bk.ch_cin)
             for s in arr.ravel()} | {d for d, _ in net.regs} | {
        s for bus in net.pos.values() for s in bus}
    for s in reads - {0, 1, fused.sink}:
        if s in written or s in net.sources:
            assert plan.slot[s] >= 2
        else:
            assert kind == "undriven" and plan.slot[s] == 0
    assert np.array_equal(np.asarray(prog.d_idx),
                          plan.slot[[d for d, _ in net.regs]])
    assert np.array_equal(np.asarray(prog.po_idx), plan.slot[
        [s for bus in net.pos.values() for s in bus]])


def test_cycle_program_writes_by_slice_only(arrays, monkeypatch):
    """The ``repro.seq.run`` span counts the rows a call writes: cycles x
    (the source block, ``CONST0``/``CONST1`` included, and every level's
    outputs, padding included), all by slice."""
    from repro.core import eval_jax

    seen = []
    real = eval_jax.span

    def record(name, *args, **stats):
        seen.append((name, stats))
        return real(name, *args, **stats)

    monkeypatch.setattr(eval_jax, "span", record)
    net = arrays[2]
    stream, _ = _schedule(net, 2, 1, 1, seed=3)
    flow.evaluate_sequential([net], [stream], use_pallas=False)
    fused = eval_jax.plan_netlist(net)
    blocks = sum(bk.n_levels * (bk.shape[1] * bk.has_luts
                                + (bk.shape[2] * bk.shape[3] + bk.shape[2])
                                * bk.has_chains) for bk in fused.buckets)
    run = [st for name, st in seen if name == "repro.seq.run"]
    assert run == [{"slice_rows": stream.shape[0]
                    * (2 + len(net.sources) + blocks), "scatter_rows": 0}]


def test_slot_level_writes_its_luts_before_its_chains():
    """A chain that reads a LUT output of its own level (a plan built by
    hand: levelization puts such a chain one level later) reads the
    value the LUT wrote, on both write paths."""
    import jax.numpy as jnp

    from repro.core import eval_jax

    # signals: 0, 1, a = 2, b = 3, lut t = a ^ b = 4, chain sum = t + b = 5
    bk = eval_jax.PlanBucket(
        n_levels=1, has_luts=True, has_chains=True,
        lut_ins=np.array([[[2, 3, 0, 0, 0, 0]]], np.int32),
        lut_tt_lo=np.array([[0b0110]], np.uint32),
        lut_tt_hi=np.array([[0b0110]], np.uint32),
        lut_out=np.array([[4]], np.int32),
        ch_a=np.array([[[4]]], np.int32), ch_b=np.array([[[3]]], np.int32),
        ch_cin=np.array([[0]], np.int32), ch_sums=np.array([[[5]]], np.int32),
        ch_cout=np.array([[6]], np.int32), ch_last=np.array([[0]], np.int32))
    fused = eval_jax.FusedPlan(n_signals=6, n_levels=1, buckets=(bk,))
    a, b = np.uint32(0b1100), np.uint32(0b1010)
    want = {4: a ^ b, 5: a ^ b ^ b}
    vals = jnp.zeros((7, 1), jnp.uint32).at[2:4, 0].set(jnp.array([a, b]))
    out = eval_jax._multi_scan(vals, fused.arrays(), fused.flags, False)
    assert {s: out[s, 0] for s in want} == want
    plan = eval_jax.slot_plan(fused, [2, 3])
    vals = jnp.zeros((plan.n_rows, 1), jnp.uint32).at[2:4, 0].set(
        jnp.array([a, b]))
    out = eval_jax._multi_scan(vals, plan.buckets, plan.flags, False,
                               body=eval_jax._slot_body)
    assert {s: out[plan.slot[s], 0] for s in want} == want


def test_evaluate_sequential_refuses_a_wrong_stream(arrays):
    net = arrays[2]
    with pytest.raises(ValueError):
        flow.evaluate_sequential(
            [net], [np.zeros((5, len(net.pis) + 1, 1), np.uint32)])
    with pytest.raises(ValueError):
        flow.evaluate_sequential(
            [net], [np.zeros((len(net.pis), 1), np.uint32)])


def test_combinational_eval_reads_q_as_a_free_input():
    """One combinational instant of a registered netlist (the suite
    evaluator) takes each Q's lanes like a primary input's."""
    net = _counter()
    en, (c0, c1) = net.pis[0], net.reg_buses["c"]
    lanes = {en: np.array([0b1100], np.uint32),
             c0: np.array([0b1010], np.uint32),
             c1: np.array([0b0110], np.uint32)}
    outs, _ = flow.evaluate_suite([net], [lanes], 1, use_pallas=False)
    d0, d1 = (d for d, _ in net.regs)
    assert int(outs[0][d0][0]) == 0b1010 ^ 0b1100
    assert int(outs[0][d1][0]) == 0b0110 ^ (0b1010 & 0b1100)


def test_single_circuit_eval_reads_q_as_a_free_input(arrays):
    """The single-circuit entry takes each Q's lanes like a primary
    input's too: every signal of a 2x2 systolic array, its primary inputs
    and registers' Q all driven, equals the oracle's instant."""
    from repro.core.eval_jax import eval_netlist_jax
    from repro.core.netlist import eval_netlist

    net = arrays[2]
    NW = 2
    rng = np.random.default_rng(12)
    lanes = {s: rng.integers(0, 2**32, NW, dtype=np.uint32)
             for s in net.sources}
    got = eval_netlist_jax(net, lanes, NW, use_pallas=False)
    ref = eval_netlist(net, {s: flow._lanes_int(v) for s, v in lanes.items()},
                       32 * NW)
    assert len(net.sources) > len(net.pis)
    for s in range(net.n_signals):
        assert flow._lanes_int(got[s]) == ref.get(s, 0), s


# ---------------------------------------------------------------------------
# packing, timing and equivalence with registers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["baseline", "dd5", "dd6"])
def test_registers_pack_into_ff_slots(arrays, arch):
    from repro.core.packing import pack

    net = arrays[4]
    p = pack(net, ARCHS[arch], seed=0)
    assert sorted(p.reg_site) == list(range(len(net.regs)))
    assert max(Counter(p.reg_site.values()).values()) <= FF_PER_ALM
    assert all(len(lb.alms) <= ARCHS[arch].alms_per_lb for lb in p.lbs)
    assert all(len(p.lb_external_ins(lb)) <= ARCHS[arch].input_budget
               for lb in range(p.n_lbs))
    # a register whose D a LUT or an adder of an ALM produces sits there
    # while that ALM has a free slot: here every one of them does
    prod = {net.lut_out[li]: ai for li, ai in p.lut_site.items()}
    for (ci, bi), ai in p.chain_site.items():
        prod[net.chains[ci].sums[bi]] = ai
    homed = [ri for ri, (d, _) in enumerate(net.regs) if d in prod]
    assert sum(p.reg_site[ri] == prod[net.regs[ri][0]] for ri in homed) \
        > 0.9 * len(homed)


@pytest.mark.parametrize("arch", ["baseline", "dd5", "dd6"])
def test_timing_with_registers_equals_the_oracle(arrays, arch):
    from repro.core.packing import pack
    from repro.core.timing import analyze, analyze_oracle
    from repro.core.timing_vec import analyze_ir

    p = pack(arrays[4], ARCHS[arch], seed=0)
    want = analyze_oracle(p)
    assert analyze(p) == want
    assert analyze_ir(p.lower_ir(), ARCHS[arch], backend="jax") == want


def test_register_paths_start_at_q_and_end_at_d():
    """Q -> one LUT -> D: the path is clock-to-Q, the LUT's input route
    and delay, then setup; the PO (the Q itself) ends earlier."""
    from repro.core.packing import pack
    from repro.core.alm import T_CLK_Q_CPS, T_SETUP_CPS
    from repro.core.timing import analyze_oracle
    from repro.core.timing_vec import analyze_ir

    net = Netlist("toggle")
    r = net.add_reg_bus("r", 1)[0]
    net.connect_reg(r, net.add_lut((r,), 0b01))        # r <- not r
    net.set_po_bus("r", [r])
    arch = ARCHS["baseline"]
    p = pack(net, arch, seed=0)
    rec = analyze_oracle(p)
    route = (arch.t_route_local if p.alm_lb[p.reg_site[0]]
             == p.alm_lb[p.lut_site[0]] else arch.t_route_global)
    want = (T_CLK_Q_CPS + T_SETUP_CPS) / 100 + route + arch.t_lbin_to_ah \
        + arch.t_lut4 + arch.t_alm_out
    assert rec["critical_path_ps"] == pytest.approx(want)
    assert analyze_ir(p.lower_ir(), arch) == rec


@pytest.mark.parametrize("arch", ["baseline", "dd5", "dd6"])
def test_registers_correspond_by_name(arrays, arch):
    from repro.core.equiv import check_pack_equivalence

    rep = check_pack_equivalence(arrays[2], ARCHS[arch], seed=0)
    assert rep["equivalent"], rep


def test_swapped_register_inputs_are_not_equivalent(arrays):
    from repro.core.equiv import (equivalence_report, reelaborate,
                                  symbolic_equivalence_report)
    from repro.core.packing import pack

    net = arrays[2]
    re = reelaborate(pack(net, ARCHS["dd5"], seed=0))
    assert equivalence_report(net, re, n_vectors=64)["equivalent"]
    # the physical registers of two source registers swap their D
    ps = {q: i for i, (_, q) in enumerate(re.phys.regs)}
    i = ps[re.sig_map[net.reg_buses["p_1_0"][0]]]
    j = ps[re.sig_map[net.reg_buses["p_1_0"][1]]]
    (di, qi), (dj, qj) = re.phys.regs[i], re.phys.regs[j]
    re.phys.regs[i], re.phys.regs[j] = (dj, qi), (di, qj)
    assert not equivalence_report(net, re, n_vectors=64)["equivalent"]
    assert not symbolic_equivalence_report(net, re)["equivalent"]


def test_unplaced_register_is_refused(arrays):
    from repro.core.equiv import ReElaborationError, reelaborate
    from repro.core.packing import pack

    p = pack(arrays[2], ARCHS["baseline"], seed=0)
    del p.reg_site[0]
    with pytest.raises(ReElaborationError):
        reelaborate(p)
