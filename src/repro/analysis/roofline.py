"""Three-term roofline model from a compiled dry-run artifact.

    compute_term    = HLO_FLOPs / (chips x peak_FLOPs)      [s]
    memory_term     = HLO_bytes / (chips x HBM_bw)          [s]
    collective_term = wire_bytes / (chips x link_bw)        [s]   (one link)

``cost_analysis()`` on the post-SPMD module is *per device*, so chips=1 in the
denominators here and the table reports per-chip seconds directly.

Collective bytes are NOT in cost_analysis: we parse the compiled HLO text and
apply ring-algorithm wire formulas per op kind (documented inline). Group size
is parsed from ``replica_groups`` (both the explicit ``{{0,1,...}}`` and the
iota ``[G,S]<=[N]`` forms).

Hardware peaks come from one table, ``PEAKS``, keyed by the device kind
JAX reports (``jax.devices()[0].device_kind``), each row with its source. A
kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import json
import re
from typing import Any


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    flops: float     # bf16 FLOP/s
    hbm_bw: float    # HBM bytes/s
    ici_bw: float    # chip-to-chip interconnect bytes/s, all links together
    ici_links: int   # interconnect links per chip
    source: str

    @property
    def link_bw(self) -> float:
        """Bytes/s over one interconnect link: the ring formulas below
        count the bytes one link carries."""
        return self.ici_bw / self.ici_links


V5E = "TPU v5 lite"  # device_kind of a TPU v5e chip

PEAKS = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
                   ici_links=4,
                   source="Google Cloud documentation, 'TPU v5e': 197 "
                          "TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 "
                          "Gbit/s chip-to-chip interconnect over 4 ICI "
                          "ports per chip (2D torus)"),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peaks of ``device_kind``; raises for a kind not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=(\{\{[^}]*\}[^,)]*\}|\[\d+,\d+\]<=\[[\d,]+\])")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return default
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(1, first.count(",") + 1)
    m2 = re.match(r"\[(\d+),(\d+)\]<=", g)
    if m2:
        return int(m2.group(2))
    return default


def parse_collectives(hlo_text: str, default_group: int = 1) -> dict:
    """Per-device wire bytes by collective kind (ring formulas).

      all-gather:         result R gathered over g -> (g-1)/g * R on the wire
      all-reduce:         2 * (g-1)/g * R   (reduce-scatter + all-gather ring)
      reduce-scatter:     (g-1)/g * input   (input = g * result)
      all-to-all:         (g-1)/g * R
      collective-permute: R
    """
    out: dict[str, dict[str, float]] = {}
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        # async pairs: count -start, skip -done (same op)
        opname = line.strip().split(" ")[0]
        if "-done" in line.split("=")[1][:40]:
            continue
        r = _shape_bytes(shape_str)
        g = _group_size(line, default_group)
        if kind == "all-gather":
            wire = (g - 1) / max(g, 1) * r
        elif kind == "all-reduce":
            wire = 2 * (g - 1) / max(g, 1) * r
        elif kind == "reduce-scatter":
            wire = (g - 1) / max(g, 1) * r * g  # input bytes = g * result
        elif kind == "all-to-all":
            wire = (g - 1) / max(g, 1) * r
        else:  # collective-permute
            wire = r
        d = out.setdefault(kind, {"count": 0, "bytes": 0.0, "wire_bytes": 0.0})
        d["count"] += 1
        d["bytes"] += r
        d["wire_bytes"] += wire
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    collectives: dict
    compute_term: float
    memory_term: float
    collective_term: float
    bottleneck: str
    model_flops: float = 0.0
    useful_ratio: float = 0.0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def analyze(
    hlo_text: str,
    *,
    model_flops: float = 0.0,
    default_group: int = 1,
) -> Roofline:
    """Roofline terms from post-SPMD HLO via the loop-aware structural model
    (repro.analysis.hlo_cost) — ``cost_analysis()`` counts while bodies once,
    so it cannot be used directly for scanned models. The terms are seconds
    on one v5e chip: a dry run models a v5e deployment."""
    from repro.analysis.hlo_cost import analyze_hlo

    pk = peaks(V5E)
    c = analyze_hlo(hlo_text, default_group=default_group)
    ct = c.flops / pk.flops
    mt = c.hbm_bytes / pk.hbm_bw
    lt = c.wire_bytes / pk.link_bw
    terms = {"compute": ct, "memory": mt, "collective": lt}
    bottleneck = max(terms, key=terms.get)
    return Roofline(
        flops=c.flops, hbm_bytes=c.hbm_bytes, wire_bytes=c.wire_bytes,
        collectives=c.collectives, compute_term=ct, memory_term=mt,
        collective_term=lt, bottleneck=bottleneck, model_flops=model_flops,
        useful_ratio=(model_flops / c.flops if c.flops else 0.0),
    )


def model_flops_train(cfg, shape) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE) — the classic useful-FLOPs yardstick."""
    n = active_param_count(cfg)
    tokens = shape.global_batch * shape.seq_len
    return 6.0 * n * tokens


def model_flops_step(cfg, shape) -> float:
    if shape.kind == "train":
        return model_flops_train(cfg, shape)
    if shape.kind == "prefill":
        n = active_param_count(cfg)
        return 2.0 * n * shape.global_batch * shape.seq_len
    # decode: one token per sequence
    n = active_param_count(cfg)
    return 2.0 * n * shape.global_batch


def active_param_count(cfg) -> int:
    """Parameters touched per token (MoE: top_k+shared experts only)."""
    from repro.models import nn as _nn
    from repro.models.steps import model_specs

    specs = model_specs(cfg)
    total = _nn.count_params(specs)
    if cfg.moe is None:
        return total

    # subtract inactive expert weights
    import math as _m

    E, K = cfg.moe.num_experts, cfg.moe.top_k
    expert_leaf = 0
    per_layer_expert = 3 * cfg.d_model * cfg.moe.d_expert  # gate/up/down
    moe_layers = 0
    P = len(cfg.mlp_pattern)
    for j in range(cfg.num_layers):
        kind = cfg.mlp_pattern[j % P]
        if j < cfg.first_k_dense:
            kind = "dense"
        if kind == "moe":
            moe_layers += 1
    inactive = moe_layers * (E - K) * per_layer_expert
    return total - inactive
