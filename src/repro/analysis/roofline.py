"""Roofline terms from dry-run artifacts, against the peaks of one chip.

    compute term    = HLO_FLOPs_global    / (chips × peak_FLOP/s)
    memory term     = HLO_bytes_global    / (chips × HBM_bw)
    collective term = collective_bytes_global / (chips × link_bw)

``cost_analysis()`` on the post-SPMD module reports *per-device* FLOPs/bytes,
so global = per-device × chips and each term reduces to per-device / peak.
MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D (MoE) per the assignment; the
ratio MODEL_FLOPS/HLO_FLOPs measures how much compiled compute is "useful"
(AdaHessian's HVP legitimately adds ≈ one extra backward pass; remat and
dispatch overheads show up here too).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Dict, Optional

from repro.configs.base import INPUT_SHAPES, ModelConfig, get_config



@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float   # bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    ici_bw: float  # chip-to-chip bytes/s per link
    source: str


# Keyed by ``jax.Device.device_kind``. A device not listed here is an error
# (``peaks``), never a default.
PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9,
        # 1,600 Gbit/s of interchip interconnect over 4 links
        ici_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI"),
}

# The chip the dry-run meshes model (v5e pods of 256 chips); dry-run
# records carry it as ``target_device_kind``.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """Published peaks of one chip of ``device_kind``; raises for a kind
    with no row in :data:`PEAKS`."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind={device_kind!r}; add a "
            f"row with its source to repro.analysis.roofline.PEAKS "
            f"(known: {sorted(PEAKS)})") from None


def active_param_count(cfg: ModelConfig) -> int:
    """N (dense) or N_active (MoE) — parameters touched per token."""
    from repro.models.registry import build_model
    from repro.nn.param import param_count, spec_leaves

    model = build_model(cfg)
    total = param_count(model.spec)
    if not cfg.moe:
        return total
    # subtract inactive experts: each routed expert has 3 matrices e_dff×d
    per_expert = 3 * cfg.e_dff * cfg.d_model
    n_moe_layers = cfg.num_layers - cfg.first_dense_layers
    inactive = n_moe_layers * (cfg.num_experts - cfg.top_k) * per_expert
    return total - inactive


def model_flops(cfg: ModelConfig, shape_name: str, kind: str) -> float:
    """6·N·D forward+backward estimate (D = tokens processed)."""
    shape = INPUT_SHAPES[shape_name]
    n = active_param_count(cfg)
    if kind.startswith("train"):
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if "prefill" in kind:
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens  # forward only
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


@dataclasses.dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    hlo_flops_global: float
    flops_ratio: Optional[float]

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=lambda k: terms[k] or 0.0)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_from_record(rec: Dict) -> Optional[Roofline]:
    if rec.get("status") != "ok":
        return None
    la = rec.get("loop_aware") or {}
    if la.get("flops_multiplier"):
        # calibrated: XLA's per-op cost model × the parser's loop multiplier
        # (cost_analysis visits while bodies once — analysis/hlo_cost.py)
        flops_d = ((rec.get("flops_per_device") or 0.0)
                   * la["flops_multiplier"])
        bytes_d = ((rec.get("bytes_per_device") or 0.0)
                   * (la.get("bytes_multiplier") or 1.0))
        coll_d = la.get("collective_total_per_device") or 0.0
    elif la.get("dot_flops_per_device"):
        flops_d = la["dot_flops_per_device"]
        bytes_d = la.get("bytes_per_device") or 0.0
        coll_d = la.get("collective_total_per_device") or 0.0
    else:
        flops_d = rec.get("flops_per_device") or 0.0
        bytes_d = rec.get("bytes_per_device") or 0.0
        coll = rec.get("collective_bytes_per_device") or {}
        coll_d = coll.get("total") or 0.0
    n = rec["devices"]
    cfg = get_config(rec["arch"])
    mf = model_flops(cfg, rec["shape"], rec.get("lowered_kind", "train"))
    # multi-pod elastic round trains k workers' sub-batches = same global D
    hlo_global = flops_d * n
    pk = peaks(rec["target_device_kind"])
    return Roofline(
        compute_s=flops_d / pk.flops,
        memory_s=bytes_d / pk.hbm_bw,
        collective_s=coll_d / pk.ici_bw,
        model_flops=mf,
        hlo_flops_global=hlo_global,
        flops_ratio=(mf / hlo_global) if hlo_global else None,
    )


def load_records(path: str):
    recs = []
    with open(path) as f:
        for line in f:
            recs.append(json.loads(line))
    # dedupe keep-last
    seen = {}
    for r in recs:
        seen[(r["arch"], r["shape"], r.get("multi_pod", False))] = r
    return list(seen.values())


def render_table(path: str, multi_pod: bool = False) -> str:
    rows = []
    head = ("| arch | shape | compute s | memory s | collective s | "
            "dominant | MODEL/HLO | suggestion |")
    sep = "|" + "---|" * 8
    rows.append(head)
    rows.append(sep)
    for rec in sorted(load_records(path),
                      key=lambda r: (r["arch"], r["shape"])):
        if rec.get("multi_pod", False) != multi_pod:
            continue
        if rec["status"] == "skipped":
            rows.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                        f"skipped | — | {rec.get('reason','')} |")
            continue
        if rec["status"] != "ok":
            rows.append(f"| {rec['arch']} | {rec['shape']} | — | — | — | "
                        f"ERROR | — | {rec.get('error','')[:60]} |")
            continue
        r = roofline_from_record(rec)
        sug = SUGGESTIONS.get(r.dominant, "")
        ratio = f"{r.flops_ratio:.2f}" if r.flops_ratio else "—"
        rows.append(
            f"| {rec['arch']} | {rec['shape']} | {r.compute_s:.4f} | "
            f"{r.memory_s:.4f} | {r.collective_s:.4f} | {r.dominant} | "
            f"{ratio} | {sug} |")
    return "\n".join(rows)


SUGGESTIONS = {
    "compute": "cut redundant FLOPs (remat policy, HVP fusion) or raise "
               "MODEL/HLO toward 1",
    "memory": "increase arithmetic intensity: fuse elementwise chains, "
              "larger per-device tiles, bf16 caches",
    "collective": "reshard to cut all-gathers (sequence-parallel residual, "
                  "expert-parallel dispatch) or overlap collectives",
}
