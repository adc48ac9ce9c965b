"""§Roofline: three-term analysis per (arch x shape x mesh).

Terms (seconds, per the assignment's formulas; TPU v5e constants):

    compute    = FLOPs / (chips * 197e12)
    memory     = HBM_bytes / (chips * 819e9)
    collective = collective_bytes / (chips * 50e9)

FLOPs / HBM bytes are **analytic** (derived from the model math and the
sharding strategy): XLA:CPU's ``cost_analysis`` counts ``scan`` bodies once
(trip counts are lost), so the compiled numbers undercount by ~L x — we report
them alongside for transparency, and take the collective *inventory* (which
ops, at what shapes) from the compiled HLO of the dry-run.  HBM modeling
assumes the flash-attention kernel (scores never hit HBM); the dry-run HLO
materializes reference attention instead, which is an XLA-CPU artifact.
"""
from __future__ import annotations

import glob
import json
import os

import numpy as np

from repro.configs.base import SHAPES, get_config

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / link

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def mesh_sizes(mesh_tag: str):
    return {"single": (256, 16, 16, 1), "multi": (512, 32, 16, 2)}[mesh_tag]
    # (chips, dp [pod*data], tp, pods)


def _attn_flops_fwd(cfg, B: int, S: int) -> float:
    """Per-step attention score+value flops, window-aware.

    Full causal layer: 4*B*S^2*H*D*0.5.  With the chunked sliding-window
    path (cfg.chunked_local_attn) a local layer computes S x 2w scores."""
    from repro.models.lm import _layer_windows
    import numpy as np

    if cfg.family == "ssm":
        return 0.0
    L, Hq, Dh = cfg.n_layers, cfg.n_heads, cfg.hd
    windows = np.asarray(_layer_windows(cfg, L))
    total = 0.0
    for w in windows:
        w = int(w)
        if cfg.chunked_local_attn and w * 2 <= S:
            total += 4 * B * S * (2 * w) * Hq * Dh
        else:
            total += 4 * B * (S ** 2) * Hq * Dh * 0.5
    return total


def analytic_terms(arch: str, shape_name: str, mesh_tag: str,
                   n_params: int, n_active: int, cfg=None,
                   fp8_expert_gather: bool = False) -> dict:
    if cfg is None:
        cfg = get_config(arch)
    shape = SHAPES[shape_name]
    chips, dp, tp, pods = mesh_sizes(mesh_tag)
    B, S = shape.global_batch, shape.seq_len
    L, d, Hq, Dh = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.hd
    bytes_p = 2  # bf16 params
    kv_elt = 1 if cfg.kv_cache_dtype == "int8" else 2

    n_attn_layers = 0 if cfg.family == "ssm" else L
    if shape.kind == "train":
        T = B * S
        flops = 6 * n_active * T
        flops += 3 * _attn_flops_fwd(cfg, B, S)
        if cfg.ssm_heads:
            flops += 3 * L * B * S * cfg.ssm_heads * cfg.ssm_head_dim \
                * cfg.ssm_state * 6
        # HBM: params fwd+bwd reads + grad w/r + opt rw + remat activations
        opt_bytes = (16 * n_params if not arch.startswith("kimi")
                     else 5 * n_params)   # adamw vs adafactor
        hbm = 4 * n_params * bytes_p + opt_bytes \
            + 4 * L * B * S * d * 2
        # collectives (global): FSDP all-gather fwd+bwd + grad reduce-scatter
        # + 2 TP all-reduces per layer on activations
        # (global bytes: ring collective moves Z*(dp-1) across the fabric)
        if fp8_expert_gather and cfg.is_moe:
            # expert weights cross the data axis at 1 B/elem (fwd + bwd
            # gathers); grad reduce-scatter stays bf16
            p_exp = (cfg.n_layers - cfg.n_dense_layers) * cfg.n_experts \
                * 3 * cfg.d_model * cfg.d_ff_expert
            p_rest = n_params - p_exp
            fsdp = (2 * (p_exp * 1 + p_rest * bytes_p)
                    + n_params * bytes_p) * (dp - 1)
        else:
            fsdp = 3 * n_params * bytes_p * (dp - 1)
        tp_ar = 2 * n_attn_layers * 2 * (B * S * d * 2) * (tp - 1) / tp
        coll = fsdp + tp_ar
        if cfg.is_moe:
            coll += 4 * B * S * d * 2 * cfg.top_k / max(cfg.top_k, 1)
    elif shape.kind == "prefill":
        T = B * S
        flops = 2 * n_active * T
        flops += _attn_flops_fwd(cfg, B, S)
        hbm = n_params * bytes_p + 2 * L * B * S * d * 2 \
            + 2 * L * B * S * cfg.n_kv_heads * Dh * kv_elt
        fsdp = n_params * bytes_p * (dp - 1) / dp
        tp_ar = 2 * n_attn_layers * (B * S * d * 2) * (tp - 1) / tp
        coll = fsdp + tp_ar
    else:  # decode: one token, full cache
        Tctx = S
        flops = 2 * n_active * B
        flops += n_attn_layers * 4 * B * Hq * Dh * Tctx
        kv_bytes = 2 * n_attn_layers * B * Tctx * cfg.n_kv_heads * Dh * kv_elt
        if cfg.family in ("ssm", "hybrid"):
            kv_bytes = 2 * L * B * cfg.ssm_heads * cfg.ssm_head_dim \
                * cfg.ssm_state * 4
            if cfg.family == "hybrid":
                w = cfg.local_window or Tctx
                kv_bytes += 2 * L * B * min(w, Tctx) * cfg.n_kv_heads * Dh \
                    * kv_elt
        hbm = n_params * bytes_p + kv_bytes
        tp_ar = 2 * n_attn_layers * (B * 1 * d * 2) * (tp - 1) / tp
        coll = tp_ar + n_params * bytes_p * 0  # weights resident (no FSDP
        # gather in decode: weights stay sharded TP-style and activations
        # all-reduce)
    return {
        "flops": flops,
        "hbm_bytes": hbm,
        "coll_bytes": coll,
        "t_compute": flops / (chips * PEAK_FLOPS),
        "t_memory": hbm / (chips * HBM_BW),
        "t_collective": coll / (chips * LINK_BW),
    }


def netlist_eval_terms(net, n_lane_words: int, plan=None) -> dict:
    """Roofline terms for one fused-evaluator pass over a netlist.

    The fused engine (``repro.core.eval_jax``) is a bitwise workload: count
    uint32 *word ops* instead of FLOPs.  Per LUT row the kernel unrolls 32
    minterms over 5 pins plus the pin-5 select (~``32*7 + 4`` word ops per
    lane word); per chain bit the ripple costs ~7 word ops.  Memory traffic
    is the level gathers/scatters against the value buffer (4 B words).
    The arithmetic intensity (ops/byte) says which side of the machine the
    evaluator saturates — on every real circuit it is compute-bound, which
    is why fusing away the per-level dispatch dominated the wall clock.

    Ops/bytes and ``padding_waste`` are summed per width bucket of the
    multi-scan plan; ``padding_waste_single_envelope`` is what the same
    circuit would waste under the old one-worst-case-envelope layout, so
    the bucketing win is visible in the CSV output.
    """
    from repro.core.eval_jax import plan_netlist

    if plan is None:
        plan = plan_netlist(net)
    N = n_lane_words
    word_ops = 0
    hbm = 0
    per_bucket = []
    for bk in plan.buckets:
        l, M, C, B = bk.shape
        M = M if bk.has_luts else 0
        C = C if bk.has_chains else 0
        B = B if bk.has_chains else 0
        lut_ops = l * M * N * (32 * 7 + 4)
        chain_ops = l * C * B * N * 7
        lut_bytes = l * (M * 6 * N * 4 + M * N * 4 + M * (4 * 2 + 24))
        chain_bytes = l * C * ((2 * B + 2) * N * 4 + (B + 1) * N * 4
                               + 4 * B * 2)
        word_ops += lut_ops + chain_ops
        hbm += lut_bytes + chain_bytes
        per_bucket.append({
            "levels": l, "M": M, "C": C, "B": B,
            "padded_lut_rows": l * M,
            "padded_chain_bits": l * C * B,
        })
    padded = plan.padded_lut_rows + plan.padded_chain_bits
    L, M, C, B = plan.envelope
    padded_single = L * M + L * C * B
    real = net.n_luts + net.n_adders
    return {
        "word_ops": word_ops,
        "hbm_bytes": hbm,
        "intensity_ops_per_byte": word_ops / max(hbm, 1),
        "t_memory": hbm / HBM_BW,
        "levels": plan.n_levels,
        "n_buckets": len(plan.buckets),
        "buckets": per_bucket,
        "padded_lut_rows": plan.padded_lut_rows,
        "padded_chain_bits": plan.padded_chain_bits,
        "real_luts": net.n_luts,
        "real_chain_bits": net.n_adders,
        "padding_waste": 1.0 - real / max(padded, 1),
        "padding_waste_single_envelope": 1.0 - real / max(padded_single, 1),
    }


def timing_program_terms(irs, n_archs: int = 1) -> dict:
    """Roofline terms for one batched static-timing pass over lowered
    PackIRs (``repro.core.pack_ir``), at ``n_archs`` delay rows.

    The vectorized analyzer is an int32 gather/add/max workload
    (centi-picosecond arrivals): per LUT row it gathers 6 arrivals + 6x3
    edge components (3 adds each), a 6-way max and 3 node adds; per
    chain bit two 3-add operand edges, a 3-way max and the carry add.
    Bytes count the arrival-buffer gathers/scatters (4 B) — intensity is
    low, so unlike the bitwise
    evaluator the timing pass is memory-bound, and batching arch rows
    amortizes the index traffic rather than the flops."""
    flops = 0
    bytes_ = 0
    levels = 0
    for ir in irs:
        m, c, b = ir.level_profile()
        levels = max(levels, ir.n_levels)
        for M, C, B in zip(m, c, b):
            flops += M * (6 * 3 + 5 + 3) + C * B * (2 * 3 + 2 + 1) + C * 3
            bytes_ += M * (6 * 4 + 6 * 4 * 2 + 4) \
                + C * B * (2 * 4 + 2 * 4 * 2 + 4) + C * (4 + 4 + 4)
    flops *= n_archs
    bytes_ *= n_archs
    return {
        "flops": flops,
        "hbm_bytes": bytes_,
        "intensity_flops_per_byte": flops / max(bytes_, 1),
        "t_memory": bytes_ / HBM_BW,
        "levels": levels,
        "n_circuits": len(irs),
        "n_archs": n_archs,
    }


def load_cells(dryrun_dir: str = DRYRUN_DIR) -> list[dict]:
    cells = []
    for path in sorted(glob.glob(os.path.join(dryrun_dir, "*.json"))):
        with open(path) as f:
            cells.append(json.load(f))
    return cells


def build_table(dryrun_dir: str = DRYRUN_DIR, mesh: str = "single"):
    rows = []
    for rec in load_cells(dryrun_dir):
        if rec.get("mesh") != mesh:
            continue
        if rec.get("status") == "skipped":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "status": "skipped", "reason": rec.get("reason")})
            continue
        if rec.get("status") != "ok":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "status": rec.get("status"),
                         "reason": rec.get("error", "")[:80]})
            continue
        t = analytic_terms(rec["arch"], rec["shape"], rec["mesh"],
                           rec["n_params"], rec["n_active_params"])
        terms = {"compute": t["t_compute"], "memory": t["t_memory"],
                 "collective": t["t_collective"]}
        dominant = max(terms, key=terms.get)
        bound = max(terms.values())
        frac = terms["compute"] / bound if bound > 0 else 0.0
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "status": "ok",
            "t_compute": t["t_compute"], "t_memory": t["t_memory"],
            "t_collective": t["t_collective"],
            "dominant": dominant,
            "roofline_frac": frac,
            "model_flops": rec.get("model_flops", 0),
            "analytic_flops": t["flops"],
            "useful_ratio": (rec.get("model_flops", 0) / t["flops"]
                             if t["flops"] else 0),
            "hlo_flops_per_dev": rec.get("cost", {}).get("flops", 0),
            "hlo_coll_bytes_per_dev": rec.get("collectives", {}).get(
                "total_bytes", 0),
            "compile_s": rec.get("compile_s"),
        })
    return rows


def to_markdown(rows: list[dict]) -> str:
    out = ["| arch | shape | compute s | memory s | collective s | "
           "dominant | frac-of-roofline | useful FLOP ratio |",
           "|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r.get("status") != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                       f"{r['status']} ({r.get('reason','')}) | — | — |")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute']:.3e} | "
            f"{r['t_memory']:.3e} | {r['t_collective']:.3e} | "
            f"{r['dominant']} | {r['roofline_frac']:.2f} | "
            f"{r['useful_ratio']:.2f} |")
    return "\n".join(out)


def main():
    from .common import emit

    for mesh in ("single", "multi"):
        rows = build_table(mesh=mesh)
        ok = [r for r in rows if r.get("status") == "ok"]
        if not ok:
            emit("roofline", 0, "no dry-run artifacts; run launch.dryrun")
            return
        worst = min(ok, key=lambda r: r["roofline_frac"])
        coll_bound = [r for r in ok if r["dominant"] == "collective"]
        emit("roofline/summary", 0,
             f"cells={len(ok)};worst={worst['arch']}/{worst['shape']}"
             f"({worst['roofline_frac']:.2f});collective_bound="
             f"{len(coll_bound)}")
        csv_path = os.path.join(DRYRUN_DIR, "..", f"roofline_{mesh}.csv")
        with open(csv_path, "w") as f:
            keys = ["arch", "shape", "status", "t_compute", "t_memory",
                    "t_collective", "dominant", "roofline_frac",
                    "useful_ratio"]
            f.write(",".join(keys) + "\n")
            for r in rows:
                f.write(",".join(str(r.get(k, "")) for k in keys) + "\n")
        emit("roofline/csv", 0, os.path.abspath(csv_path))


if __name__ == "__main__":
    main()
