"""Roofline terms from a compiled dry-run artifact (no real hardware).

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_operand_bytes_per_device / ICI_link_bw

FLOPs/bytes come from ``compiled.cost_analysis()`` (the partitioned module
is per-device, so no further division by chip count is needed). Collective
bytes are parsed from ``compiled.as_text()``: for each
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
op we recover *operand* bytes from the (per-device) result shape and the
replica-group size printed on the same line.

Hardware constants come from :data:`PEAKS`, one row per ``device_kind``
as JAX reports it; a kind without a row is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

__all__ = ["PEAKS", "peaks", "RooflineReport", "collective_bytes", "analyze"]

# Published per-chip peaks by ``jax.devices()[0].device_kind``. TPU v5e
# (JAX kind "TPU v5 lite"), Google Cloud documentation "TPU v5e": 197
# TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect —
# ici_bw is that per chip over its 4 links, 50 GB/s each.
PEAKS = {
    "TPU v5 lite": dict(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 0.5, "u4": 0.5,
}

_COLL_RE = re.compile(
    r"=\s+(.+?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(",
)
_TYPE_RE = re.compile(r"([a-z]+[0-9a-z]*)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_SET_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _type_bytes(type_str: str) -> float:
    total = 0.0
    for dt, dims in _TYPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]<=[...]
    m = _GROUPS_SET_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


def collective_bytes(hlo_text: str) -> dict[str, Any]:
    """Per-device operand bytes of every collective, by op kind."""
    bytes_by_kind: dict[str, float] = {}
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done" in line and "(" in line:
            continue  # async completion: counted at -start
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_t, kind, _ = m.groups()
        rb = _type_bytes(result_t)
        gs = _group_size(line)
        if kind == "all-gather":
            ob = rb / max(gs, 1)
        elif kind == "reduce-scatter":
            ob = rb * gs
        else:  # all-reduce / all-to-all / collective-permute: same shape
            ob = rb
        bytes_by_kind[kind] = bytes_by_kind.get(kind, 0.0) + ob
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "total": sum(bytes_by_kind.values()),
        "by_kind": bytes_by_kind,
        "counts": counts,
    }


@dataclasses.dataclass
class RooflineReport:
    device_kind: str
    arch: str
    shape: str
    mesh: str
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_detail: dict
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float  # 6·N(_active)·tokens, global
    useful_frac: float  # model_flops / (flops_per_device * n_devices)
    mem_stats: dict
    hbm_top: list  # top (op, bytes) HBM contributors
    coll_top: list  # top (comp, kind, bytes, mult) collective sites

    def row(self) -> str:
        return (
            f"{self.arch:>18s} {self.shape:>11s} {self.mesh:>9s} "
            f"{self.t_compute*1e3:9.3f} {self.t_memory*1e3:9.3f} "
            f"{self.t_collective*1e3:9.3f}  {self.bottleneck:<10s} "
            f"{self.useful_frac*100:6.1f}%"
        )


def analyze(
    arch: str,
    shape: str,
    mesh_name: str,
    n_devices: int,
    compiled,
    model_flops: float,
    device_kind: str,
) -> RooflineReport:
    """Roofline terms of ``compiled`` priced at ``device_kind``'s peaks."""
    hw = peaks(device_kind)
    # loop-aware HLO cost model (launch/hlo_analysis.py): XLA's own
    # cost_analysis() counts while (lax.scan) bodies once, undercounting a
    # scanned 56-layer trunk ~56x. Validated exact on known programs.
    from repro.launch.hlo_analysis import analyze_hlo

    text = compiled.as_text()
    hc = analyze_hlo(text)
    flops = hc.flops
    byts = hc.hbm_bytes
    coll = {
        "total": hc.coll_bytes,
        "by_kind": hc.coll_by_kind,
        "counts": hc.coll_counts,
        "xla_once_counted": collective_bytes(text)["total"],
    }
    t_c = flops / hw["peak_flops"]
    t_m = byts / hw["hbm_bw"]
    t_x = coll["total"] / hw["ici_bw"]
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)
    ms = compiled.memory_analysis()
    mem = {
        "args_gb": ms.argument_size_in_bytes / 2**30,
        "temp_gb": ms.temp_size_in_bytes / 2**30,
        "out_gb": ms.output_size_in_bytes / 2**30,
        "alias_gb": ms.alias_size_in_bytes / 2**30,
    }
    return RooflineReport(
        device_kind=device_kind,
        arch=arch,
        shape=shape,
        mesh=mesh_name,
        flops_per_device=flops,
        bytes_per_device=byts,
        coll_bytes_per_device=coll["total"],
        coll_detail=coll,
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_frac=(model_flops / (flops * n_devices)) if flops else 0.0,
        mem_stats=mem,
        hbm_top=hc.top_hbm(8),
        coll_top=[
            (c[:60], k, b, m) for c, k, b, m in hc.top_collectives(8)
        ],
    )
