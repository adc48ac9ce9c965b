"""§Perf hillclimbing driver: run baseline + optimized variants of the three
selected cells, re-lower/re-compile each, and log
hypothesis -> change -> before -> after.

Selected cells (from the §Roofline table):
  1. kimi-k2-1t-a32b x train_4k     — most collective-bound (FSDP gathers)
  2. deepseek-moe-16b x decode_32k  — worst roofline fraction (KV-cache BW)
  3. hymba-1.5b x train_4k          — most paper-representative (two
     concurrent mixer primitives per layer; SWA layers pay full S^2)

Each variant is re-lowered and re-compiled through the same dry-run path
(proving the optimization actually compiles on the production mesh) and the
analytic roofline terms quantify the delta; the compiled HLO collective
inventory is the cross-check.

NOTE: the model cells must run in a fresh process (``run_variant`` imports
launch.dryrun, which sets the 512-device XLA flag on first use).
"""
import dataclasses
import json
import os

from repro.configs.base import get_config
from repro.train.optimizer import OptConfig
from repro.train.step import TrainConfig

from .roofline import analytic_terms

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "perf")


def run_variant(tag, arch, shape, cfg=None, tcfg=None, force=False):
    from repro.launch import dryrun  # sets the 512-device XLA flag

    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, f"{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    record, lowered = dryrun.lower_cell(arch, shape, False, cfg=cfg,
                                        tcfg=tcfg)
    record = dryrun.compile_cell(record, lowered)
    t = analytic_terms(arch, shape, "single", record["n_params"],
                       record["n_active_params"],
                       cfg=cfg or get_config(arch),
                       fp8_expert_gather=bool(tcfg and
                                              tcfg.fp8_expert_gather))
    record["terms"] = {k: t[k] for k in ("t_compute", "t_memory",
                                         "t_collective", "flops",
                                         "hbm_bytes", "coll_bytes")}
    record["tag"] = tag
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def show(rec):
    t = rec["terms"]
    dom = max(("t_compute", "t_memory", "t_collective"), key=lambda k: t[k])
    coll = rec.get("collectives", {})
    kinds = {k: v["bytes"] for k, v in coll.items()
             if isinstance(v, dict)}
    print(f"{rec['tag']:34s} compute={t['t_compute']:.3e}s "
          f"memory={t['t_memory']:.3e}s coll={t['t_collective']:.3e}s "
          f"dominant={dom[2:]} | HLO coll/dev: {kinds}", flush=True)
    return t


def main():
    print("== cell 1: kimi-k2 train_4k (collective-bound) ==")
    base = run_variant("kimi_train_base", "kimi-k2-1t-a32b", "train_4k")
    show(base)
    # iteration 1: fp8 expert-weight FSDP gathers
    t8 = TrainConfig(opt=OptConfig(name="adafactor"), fp8_expert_gather=True)
    v1 = run_variant("kimi_train_fp8gather", "kimi-k2-1t-a32b", "train_4k",
                     tcfg=t8)
    show(v1)

    print("== cell 2: deepseek decode_32k (memory-bound) ==")
    base2 = run_variant("deepseek_decode_base", "deepseek-moe-16b",
                        "decode_32k")
    show(base2)
    cfg_kv8 = dataclasses.replace(get_config("deepseek-moe-16b"),
                                  kv_cache_dtype="int8")
    v2 = run_variant("deepseek_decode_kv8", "deepseek-moe-16b", "decode_32k",
                     cfg=cfg_kv8)
    show(v2)

    print("== cell 3: hymba train_4k (paper-representative) ==")
    base3 = run_variant("hymba_train_base", "hymba-1.5b", "train_4k")
    show(base3)
    # it 3.1 (REFUTED at S=4k): chunked SWA is flops-neutral when 2w == S/2
    cfg_sw = dataclasses.replace(get_config("hymba-1.5b"),
                                 chunked_local_attn=True, unroll_layers=True)
    v3 = run_variant("hymba_train_chunked_swa", "hymba-1.5b", "train_4k",
                     cfg=cfg_sw)
    show(v3)
    # it 3.2: chunked-dual SSD scan — 4096 serial recurrences -> 32 dense
    # chunk steps (MXU-friendly); flops ~equal, serialization /128
    cfg_ssd = dataclasses.replace(get_config("hymba-1.5b"), ssd_chunk=128)
    v3b = run_variant("hymba_train_ssd_chunked", "hymba-1.5b", "train_4k",
                      cfg=cfg_ssd)
    show(v3b)
    cfg_m = dataclasses.replace(get_config("mamba2-2.7b"), ssd_chunk=128)
    v3c = run_variant("mamba2_train_ssd_chunked", "mamba2-2.7b", "train_4k",
                      cfg=cfg_m)
    show(v3c)
    b3c = run_variant("mamba2_train_base", "mamba2-2.7b", "train_4k")
    show(b3c)

    # combined: kv8 + chunked swa also helps gemma2 prefill (bonus check)
    cfg_g2 = dataclasses.replace(get_config("gemma2-2b"),
                                 chunked_local_attn=True, unroll_layers=True)
    v4 = run_variant("gemma2_prefill_chunked", "gemma2-2b", "prefill_32k",
                     cfg=cfg_g2)
    show(v4)
    b4 = run_variant("gemma2_prefill_base", "gemma2-2b", "prefill_32k")
    show(b4)


if __name__ == "__main__":
    main()
