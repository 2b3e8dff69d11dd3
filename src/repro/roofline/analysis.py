"""Roofline terms from a compiled (dry-run) executable — TPU v5e targets.

  compute_s    = HLO_FLOPs_per_device / peak_FLOPs
  memory_s     = HLO_bytes_per_device / HBM_bw
  collective_s = collective_bytes_per_device / ICI_link_bw

``cost_analysis()`` on the SPMD-partitioned module reports *per-device*
FLOPs/bytes (verified: an N-way sharded matmul reports 1/N of global FLOPs),
so the brief's "HLO_FLOPs / (chips × peak)" identity holds with
HLO_FLOPs(global) = per_device × chips.

collective_bytes comes from parsing the compiled HLO: result bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(async -start counted once, -done skipped), weighted by a per-op ring-cost
factor (all-reduce = 2x: reduce-scatter + all-gather).
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

# --- TPU v5e hardware constants (per chip) ---
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_LINK_BW = 50e9  # per link per direction (~50 GB/s)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_FACTOR = {
    "all-gather": 1.0,
    "all-reduce": 2.0,  # ring: reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\)|[a-z0-9\[\],{}\s]+?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-op-kind result bytes (per device) from HLO text."""
    out: Dict[str, float] = {k: 0.0 for k in _COLL_FACTOR}
    count: Dict[str, int] = {k: 0 for k in _COLL_FACTOR}
    for line in hlo_text.splitlines():
        if "-done" in line:
            continue
        m = _OP_RE.search(line)
        if not m:
            continue
        result_type, op, _ = m.groups()
        out[op] += _shape_bytes(result_type)
        count[op] += 1
    return {
        "bytes_by_op": out,
        "counts": count,
        "weighted_bytes": sum(out[k] * _COLL_FACTOR[k] for k in out),
    }


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    num_devices: int
    # per-device measurements
    flops: float
    bytes_accessed: float  # HLO-walker bytes (CPU-lowered upper bound)
    coll_weighted_bytes: float
    coll_by_op: Dict[str, float]
    coll_counts: Dict[str, int]
    # memory (per device)
    arg_bytes: int = 0
    temp_bytes: int = 0
    output_bytes: int = 0
    alias_bytes: int = 0
    # analytic HBM traffic (the memory-term source; see analytic_hbm_bytes)
    hbm_bytes: float = 0.0
    coll_bf16wire_bytes: float = 0.0  # TPU-wire-corrected (see hlo_cost)
    # model accounting
    model_flops_global: float = 0.0
    notes: str = ""

    @property
    def compute_s(self) -> float:
        return self.flops / PEAK_FLOPS_BF16

    @property
    def memory_s(self) -> float:
        src = self.hbm_bytes if self.hbm_bytes > 0 else self.bytes_accessed
        return src / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_weighted_bytes / ICI_LINK_BW

    @property
    def collective_bf16wire_s(self) -> float:
        src = self.coll_bf16wire_bytes or self.coll_weighted_bytes
        return src / ICI_LINK_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step estimate: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs(global) — remat/redundancy waste meter."""
        total = self.flops * self.num_devices
        return self.model_flops_global / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        denom = self.step_time_s * PEAK_FLOPS_BF16 * self.num_devices
        return self.model_flops_global / denom if denom else 0.0

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 collective_s=self.collective_s,
                 collective_bf16wire_s=self.collective_bf16wire_s,
                 dominant=self.dominant,
                 step_time_s=self.step_time_s, mfu=self.mfu,
                 useful_flops_fraction=self.useful_flops_fraction)
        return d


def analytic_hbm_bytes(cfg, shape, mesh_axis_sizes: Dict[str, int],
                       arg_bytes: float, out_bytes: float,
                       alias_bytes: float = 0.0) -> float:
    """Per-device HBM traffic model for the memory roofline term.

    The CPU-lowered HLO fuses far less than TPU, so walker bytes overstate
    HBM traffic by ~50×; this closed-form model is the honest TPU estimate:
      train:   read+write all args (params/opt/grads, aliased) + activation
               carries r/w (Megatron-SP sharded) + logits chunks (fwd+bwd)
      prefill: read args + write caches + carries
      decode:  read args (params + whole KV cache) + write logits/new slot
    """
    tp = mesh_axis_sizes.get("model", 1)
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh_axis_sizes.get(a, 1)
    dtype_bytes = 2 if cfg.dtype == "bfloat16" else 4
    b_loc = max(shape.global_batch // dp, 1)
    if shape.kind == "train":
        carry = b_loc * shape.seq_len * cfg.d_model * dtype_bytes / tp
        carries = 2.0 * carry * cfg.num_periods
        logits = 2.0 * b_loc * shape.seq_len * (cfg.vocab_size / tp) * 4.0
        return 2.0 * arg_bytes + carries + logits
    if shape.kind == "prefill":
        carry = b_loc * shape.seq_len * cfg.d_model * dtype_bytes / tp
        return arg_bytes + out_bytes + 2.0 * carry * cfg.num_periods
    # decode: read weights + the full KV cache; aliased cache writes are
    # in-place (one slot), so only the non-aliased output counts
    return arg_bytes + max(out_bytes - alias_bytes, 0.0)


def model_flops(cfg, shape) -> float:
    """6·N_active·tokens (train) or 2·N_active·tokens (inference), global."""
    n = cfg.active_param_count()
    toks = shape.tokens_per_step
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * toks


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()``: XLA's flops/bytes estimate as a dict."""
    return compiled.cost_analysis()


def from_compiled(arch: str, shape_name: str, mesh_name: str, num_devices: int,
                  compiled, model_flops_global: float = 0.0,
                  notes: str = "") -> RooflineReport:
    """Trip-corrected HLO walker numbers (roofline/hlo_cost.py) — XLA's own
    cost_analysis counts while-loop bodies once (scan-over-layers would be
    under-reported ~num_layers×); raw values kept in notes for reference."""
    from repro.roofline import hlo_cost

    cost = cost_analysis_dict(compiled)
    walk = hlo_cost.analyze(compiled.as_text())
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    notes = (notes + f" | xla_once: flops={cost.get('flops', 0.0):.3e} "
             f"bytes={cost.get('bytes accessed', 0.0):.3e}")
    return RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name, num_devices=num_devices,
        flops=float(walk["flops"]), bytes_accessed=float(walk["bytes"]),
        coll_weighted_bytes=float(walk["weighted_coll_bytes"]),
        coll_bf16wire_bytes=float(walk.get("weighted_coll_bytes_bf16wire",
                                           walk["weighted_coll_bytes"])),
        coll_by_op=walk["coll_by_op"], coll_counts=walk["coll_counts"],
        arg_bytes=getattr(mem, "argument_size_in_bytes", 0) if mem else 0,
        temp_bytes=getattr(mem, "temp_size_in_bytes", 0) if mem else 0,
        output_bytes=getattr(mem, "output_size_in_bytes", 0) if mem else 0,
        alias_bytes=getattr(mem, "alias_size_in_bytes", 0) if mem else 0,
        model_flops_global=model_flops_global, notes=notes)
