"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model: per-chip peaks keyed by ``device_kind`` (:data:`PEAKS`,
:func:`peaks_for`); a device that is not in the table is an error.

IMPORTANT CAVEAT (validated empirically, see EXPERIMENTS.md §Dry-run):
XLA's HloCostAnalysis counts a while-loop BODY exactly once, independent of
trip count.  Our training path is scan-over-layers, so raw
``cost_analysis()`` under-reports flops/bytes/collectives by ~n_layers.
We therefore use three sources:

  * compute term    — ANALYTIC flops (matmul 2·N_active·D + attention
    quadratic/window terms + SSM scan term; ×3 for training), the standard
    algorithmic-roofline numerator.  Raw cost_analysis flops are recorded
    alongside for transparency.
  * memory term     — traffic proxy from ``memory_analysis()`` (which IS
    exact: argument + output + 2×temp arena per device ≈ one read + one
    write of every live buffer).
  * collective term — computation-aware HLO parsing: collectives inside
    while BODIES are multiplied by the loop trip count (layer count for the
    layer scans), collectives in the entry / conditional branches count
    once.  Ring-model wire bytes per device:
      all-reduce 2·s·(g−1)/g, all-gather s·(g−1)/g, reduce-scatter s·(g−1),
      all-to-all s·(g−1)/g, collective-permute s.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``.
#: "TPU v5 lite" (TPU v5e) — Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
#: interconnect, i.e. 50 GB/s on each of its four ICI links.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` row of ``device_kind``; unknown kinds raise
    instead of borrowing another chip's numbers."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|\w+\[[^\]]*\]\S*)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*\}[^,]*\}|\[[\d,]+\]<=\[[^\]]*\])")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->",
                      re.MULTILINE)
_WHILE_BODY_RE = re.compile(r"body=%?([\w\.\-]+)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if not m:
        return 2
    g = m.group(1)
    if g.startswith("{{"):
        first = g[2:].split("}")[0]
        return max(len([x for x in first.split(",") if x.strip() != ""]), 1)
    inner = g[1:g.index("]")]
    parts = [int(x) for x in inner.split(",")]
    return parts[-1] if parts else 2


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """computation name -> its text block.

    Optimized-HLO layout: every computation opens with a header line
    ``[ENTRY ]%name (params...) -> result {`` and closes with a bare ``}``
    at column 0; computations never nest, so no brace counting is needed
    (shape layouts like ``{3,2,1,0}`` inside bodies stay balanced per line).
    """
    comps: Dict[str, str] = {}
    name: Optional[str] = None
    buf: list = []
    for line in hlo_text.splitlines():
        if name is None:
            if line.rstrip().endswith("{") and " -> " in line:
                hdr = line.strip()
                if hdr.startswith("ENTRY"):
                    hdr = hdr[len("ENTRY"):].strip()
                name = hdr.split(" ", 1)[0].split("(", 1)[0].lstrip("%")
                buf = []
        else:
            if line.rstrip() == "}":
                comps[name] = "\n".join(buf)
                name = None
            else:
                buf.append(line)
    return comps


def collective_stats(hlo_text: str, loop_trip: int = 1) -> Dict:
    """Per-device wire bytes.  Collectives inside while bodies (and their
    transitively-called computations) are multiplied by ``loop_trip``."""
    comps = _split_computations(hlo_text)
    body_names = set()
    for text in comps.values():
        for m in _WHILE_BODY_RE.finditer(text):
            body_names.add(m.group(1))
    # transitive closure: computations called from a while body also loop
    called_re = re.compile(r"(?:calls=|to_apply=|body=|condition=|"
                           r"branch_computations=\{)%?([\w\.\-]+)")
    looped = set(body_names)
    frontier = list(body_names)
    while frontier:
        nm = frontier.pop()
        for m in called_re.finditer(comps.get(nm, "")):
            c = m.group(1)
            if c not in looped:
                looped.add(c)
                frontier.append(c)

    per_kind: Dict[str, float] = {}
    raw_bytes = 0.0
    wire = 0.0
    count = 0
    for name, text in comps.items():
        mult = loop_trip if name in looped else 1
        for line in text.splitlines():
            m = _OP_RE.search(line)
            if not m or "-done(" in line:
                continue
            size = _shape_bytes(m.group(1))
            g = _group_size(line)
            kind = m.group(2)
            if kind == "all-reduce":
                w = 2.0 * size * (g - 1) / g
            elif kind == "all-gather":
                w = size * (g - 1) / g
            elif kind == "reduce-scatter":
                w = float(size) * (g - 1)
            elif kind == "all-to-all":
                w = size * (g - 1) / g
            else:
                w = float(size)
            raw_bytes += size * mult
            wire += w * mult
            count += 1
            per_kind[kind] = per_kind.get(kind, 0.0) + w * mult
    return {"n_collectives": count, "result_bytes": raw_bytes,
            "wire_bytes_per_device": wire, "per_kind_wire_bytes": per_kind}


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   wire_bytes_per_dev: float, device_kind: str) -> Dict:
    peaks = peaks_for(device_kind)
    t_c = flops_per_dev / peaks["flops"]
    t_m = bytes_per_dev / peaks["hbm_bw"]
    t_x = wire_bytes_per_dev / peaks["link_bw"]
    dominant = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
                   key=lambda kv: kv[1])[0]
    return {"compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
            "dominant": dominant}


def model_flops(n_params_active: float, tokens: float) -> float:
    """MODEL_FLOPS = 6 * N * D (dense) / 6 * N_active * D (MoE)."""
    return 6.0 * n_params_active * tokens


# ---------------------------------------------------------------------------
# analytic FLOPs (algorithmic roofline numerator)
# ---------------------------------------------------------------------------

def analytic_flops(cfg, shape, n_params_active: float) -> float:
    """Global FLOPs for one step: matmul (2·N_active per token) + attention
    score/value terms + SSM scan term; training multiplies by 3 (bwd≈2×fwd).
    """
    S, B = shape.seq_len, shape.global_batch
    kind = shape.kind
    tokens = B * S if kind != "decode" else B
    total = 2.0 * n_params_active * tokens

    # attention context terms
    from repro.models.model import layer_kinds  # local import, no cycle
    kinds = layer_kinds(cfg)
    H, hd = cfg.n_heads, cfg.hd
    if cfg.mixer in ("gqa", "mla", "hybrid"):
        for k in kinds:
            if kind == "decode":
                ctx = min(S, cfg.sliding_window or S) if not k.is_global else S
                total += 4.0 * B * ctx * H * hd
            else:
                if k.is_global or cfg.sliding_window is None:
                    total += 4.0 * B * S * S * H * hd * 0.5  # causal half
                else:
                    total += 4.0 * B * S * cfg.sliding_window * H * hd
    if cfg.is_encdec:
        F = cfg.n_frontend_tokens
        total += cfg.encoder_layers * 4.0 * B * F * F * H * hd  # enc self
        total += cfg.n_layers * 4.0 * B * (S if kind != "decode" else 1) \
            * F * H * hd                                        # cross
    if cfg.mixer in ("mamba", "hybrid"):
        E = cfg.ssm_expand * cfg.d_model
        total += cfg.n_layers * 10.0 * tokens * E * cfg.ssm_state
    if kind == "train":
        total *= 3.0
    return total
