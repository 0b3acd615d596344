"""Roofline-term extraction from a compiled dry-run artifact.

    compute term    = HLO_FLOPs_per_device / peak_FLOPs
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = wire_bytes_per_device / (links * link_bw)

cost_analysis() is already per-device post-SPMD. Collective bytes are parsed
from compiled.as_text(): each collective's RESULT shape + replica-group size
-> ring-algorithm wire bytes per participant:
    all-gather      out * (g-1)/g
    all-reduce      2 * out * (g-1)/g
    reduce-scatter  out * (g-1)          (operand = out*g)
    all-to-all      out * (g-1)/g
    collective-permute  out
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect — taken
# here as 4 ICI links of 50 GB/s). jax names a v5e "TPU v5 lite".
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes": 16e9,
                    "hbm_bw": 819e9, "ici_links": 4, "ici_link_bw": 50e9},
}


def device_peaks(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; a kind without published
    peaks in ``DEVICE_PEAKS`` is an error, never a default."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(DEVICE_PEAKS)}") from None

_DTYPE_BYTES = {"pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*(?:\()?((?:pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|"
    r"s32|u32|s64|u64|c64|c128)\[[\d,]*\][^)]*?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|"
                       r"s64|u64|c64|c128)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([\d,]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    wire_bytes: float = 0.0
    by_kind: dict = field(default_factory=dict)

    def add(self, kind: str, b: float):
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.by_kind[kind] = self.by_kind.get(kind, 0.0) + b
        self.wire_bytes += b


def _shape_bytes(type_str: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        out_bytes = _shape_bytes(m.group(2))
        kind = m.group(3)
        g = 1
        gm = _GROUPS_RE.search(line)
        if gm:
            ids = gm.group(1)
            g = ids.count(",") + 1 if ids else 1
        else:
            gi = _GROUPS_IOTA_RE.search(line)
            if gi:
                g = int(gi.group(2))
        if g <= 1 and kind != "collective-permute":
            continue
        if kind == "all-gather":
            wire = out_bytes * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2.0 * out_bytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = out_bytes * (g - 1)
        elif kind == "all-to-all":
            wire = out_bytes * (g - 1) / g
        else:
            wire = out_bytes
        stats.add(kind, wire)
    return stats


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops_per_dev: float
    bytes_per_dev: float
    wire_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops_total: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * n_dev)
    mem_per_dev_gb: float
    collectives: dict
    counts: dict

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_ms": round(self.compute_s * 1e3, 3),
            "memory_ms": round(self.memory_s * 1e3, 3),
            "collective_ms": round(self.collective_s * 1e3, 3),
            "bottleneck": self.bottleneck,
            "useful_ratio": round(self.useful_ratio, 3),
            "mem_gb": round(self.mem_per_dev_gb, 2),
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "wire_bytes_per_dev": self.wire_bytes_per_dev,
            "counts": self.counts,
        }


def extract_raw(compiled) -> dict:
    """Per-device (flops, bytes, wire bytes, per-kind breakdown)."""
    ca = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "wire_bytes": coll.wire_bytes,
        "by_kind": coll.by_kind,
        "counts": coll.counts,
    }


def extrapolate_raw(raw1: dict, raw2: dict, n_layers: int) -> dict:
    """Linear layer-count extrapolation from two loop-free probes (L=1, L=2):
    t(L) = t(1) + (t(2) - t(1)) * (L - 1). Exact for homogeneous stacks —
    embedding / loss / optimizer are the intercept."""
    L = n_layers
    out = {}
    for k in ("flops", "bytes", "wire_bytes"):
        out[k] = max(0.0, raw1[k] + (raw2[k] - raw1[k]) * (L - 1))
    kinds = set(raw1["by_kind"]) | set(raw2["by_kind"])
    out["by_kind"] = {k: max(0.0, raw1["by_kind"].get(k, 0.0)
                             + (raw2["by_kind"].get(k, 0.0)
                                - raw1["by_kind"].get(k, 0.0)) * (L - 1))
                      for k in kinds}
    out["counts"] = {k: int(max(0, raw1["counts"].get(k, 0)
                                + (raw2["counts"].get(k, 0)
                                   - raw1["counts"].get(k, 0)) * (L - 1)))
                     for k in set(raw1["counts"]) | set(raw2["counts"])}
    return out


def memory_gb(compiled) -> float:
    ma = compiled.memory_analysis()
    if ma is None:
        return 0.0
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2.0**30


def roofline_from_raw(raw: dict, *, arch: str, shape: str, mesh_name: str,
                      n_dev: int, model_flops: float, mem_gb: float,
                      device_kind: str) -> Roofline:
    peaks = device_peaks(device_kind)
    compute_s = raw["flops"] / peaks["bf16_flops"]
    memory_s = raw["bytes"] / peaks["hbm_bw"]
    collective_s = raw["wire_bytes"] / (peaks["ici_links"]
                                        * peaks["ici_link_bw"])
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = model_flops / max(raw["flops"] * n_dev, 1.0)
    return Roofline(arch=arch, shape=shape, mesh=mesh_name,
                    flops_per_dev=raw["flops"], bytes_per_dev=raw["bytes"],
                    wire_bytes_per_dev=raw["wire_bytes"],
                    compute_s=compute_s, memory_s=memory_s,
                    collective_s=collective_s, bottleneck=bottleneck,
                    model_flops_total=model_flops, useful_ratio=useful,
                    mem_per_dev_gb=mem_gb,
                    collectives={k: round(v / 2**20, 2)
                                 for k, v in raw["by_kind"].items()},
                    counts=raw["counts"])

