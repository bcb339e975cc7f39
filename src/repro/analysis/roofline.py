"""Roofline-term derivation from a compiled dry-run artifact.

Chip peaks come from one table, ``PEAKS``, keyed by
``jax.Device.device_kind``; ``peaks(kind)`` raises for a device that is
not in it — there is no default chip.

``compiled.cost_analysis()`` and ``compiled.as_text()`` of an SPMD
module are per-partition, so with

    flops_dev, bytes_dev, wire_dev  = per-device per-step quantities

the three terms (seconds, global step time lower bounds) are

    compute    = flops_dev / peak_flops        (= HLO_FLOPs/(chips·peak))
    memory     = bytes_dev / hbm_bw            (= HLO_bytes/(chips·bw))
    collective = wire_dev  / link_bw           (= coll_bytes/(chips·link))

(the chips cancel because the per-partition module already divides the
global work by the device count). MODEL_FLOPS uses 6·N·D (train) or
2·N·D (forward-only), with N = active params for MoE; the ratio
MODEL_FLOPS / (flops_dev × chips) exposes remat/padding/redundancy
waste.
"""
from __future__ import annotations

import dataclasses
import json

from repro.analysis.hlo import collective_stats

# Published per-chip peaks. Source: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect (four links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops": 197e12,    # bf16 FLOP/s per chip
        "hbm_bw": 819e9,         # bytes/s per chip
        "link_bw": 50e9,         # bytes/s per ICI link
    },
}


def peaks(device_kind: str) -> dict:
    """The ``PEAKS`` entry of one ``device_kind``; an unknown device is
    an error, never a borrowed entry."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    flops_per_dev: float
    bytes_per_dev: float
    wire_bytes_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    useful_ratio: float          # MODEL_FLOPS / global HLO flops
    peak_fraction: float         # compute_s / max(term) — roofline fraction
    collectives: dict
    memory_analysis: dict
    note: str = ""

    def to_json(self):
        return dataclasses.asdict(self)

    def summary(self) -> str:
        return (f"{self.arch:>22s} {self.shape:>13s} {self.mesh:>5s} | "
                f"C {self.compute_s:9.3e}s M {self.memory_s:9.3e}s "
                f"X {self.collective_s:9.3e}s -> {self.dominant:10s} | "
                f"useful {self.useful_ratio:6.1%} roofline "
                f"{self.peak_fraction:6.1%}")


def _mem_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:                        # backend-dependent
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def analyze(compiled, *, arch: str, shape: str, mesh_name: str,
            n_devices: int, model_flops: float, device_kind: str,
            note: str = "") -> RooflineReport:
    """Roofline terms of ``compiled`` against the peaks of the chip
    ``device_kind`` names (the target chip, which a dry run compiles
    for on placeholder devices)."""
    from repro.compat import cost_analysis_dict
    hw = peaks(device_kind)
    cost = cost_analysis_dict(compiled)
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    coll = collective_stats(hlo)
    wire_dev = float(coll["total"]["wire_bytes"])

    compute_s = flops_dev / hw["peak_flops"]
    memory_s = bytes_dev / hw["hbm_bw"]
    collective_s = wire_dev / hw["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    global_flops = flops_dev * n_devices
    useful = model_flops / global_flops if global_flops else 0.0
    bound = max(terms.values())
    peak_fraction = (compute_s / bound) if bound > 0 else 0.0

    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, n_devices=n_devices,
        flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
        wire_bytes_per_dev=wire_dev, compute_s=compute_s, memory_s=memory_s,
        collective_s=collective_s, dominant=dominant,
        model_flops=model_flops, useful_ratio=useful,
        peak_fraction=peak_fraction, collectives=coll,
        memory_analysis=_mem_analysis_dict(compiled), note=note)


def estimate_model_flops(family: str, cfg, shape) -> float:
    """Napkin MODEL_FLOPS per step (global), per family."""
    if family == "lm":
        n_active = cfg.n_active_params()
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            return 6.0 * n_active * tokens
        if shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            return 2.0 * n_active * tokens
        # decode: one token per sequence + attention over the cache
        attn = (2.0 * 2.0 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim_
                * shape.seq_len * shape.global_batch)
        return 2.0 * n_active * shape.global_batch + attn
    if family == "gnn":
        n = shape.extra("n_nodes")
        e = shape.extra("n_edges")
        b = shape.extra("batch", 1)
        if shape.name == "minibatch_lg":
            bn = shape.extra("batch_nodes")
            f = shape.extra("fanout")
            n = bn * (1 + f[0] + f[0] * f[1])
            e = bn * f[0] * (1 + f[1])
        d = cfg.head_hidden()
        # per layer: node transform (N·d_in·d_out GEMM) + edge traffic
        node_flops = 2.0 * n * b * max(cfg.d_in, d) * d
        edge_flops = 4.0 * e * b * d
        return 3.0 * cfg.n_layers * (node_flops + edge_flops)  # fwd+bwd
    if family == "recsys":
        b = shape.global_batch
        mlp = 0
        dims = (cfg.n_dense,) + cfg.bot_mlp
        mlp += sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        f = 1 + cfg.n_sparse
        d_int = cfg.embed_dim + f * (f - 1) // 2
        dims = (d_int,) + cfg.top_mlp
        mlp += sum(2 * a * c for a, c in zip(dims[:-1], dims[1:]))
        inter = 2 * f * f * cfg.embed_dim
        factor = 3.0 if shape.kind == "train" else 1.0
        flops = factor * b * (mlp + inter)
        if shape.name == "retrieval_cand":
            flops += 2.0 * shape.extra("n_candidates") * cfg.embed_dim
        return flops
    if family == "sssp":
        # relaxation work: ~4 int-ops per edge per sweep; sweeps ~ ln(V)
        import math
        e = cfg.n_nodes * cfg.avg_degree
        sweeps = max(4, int(math.log(max(cfg.n_nodes, 2))))
        return 4.0 * e * sweeps * cfg.n_sources
    raise ValueError(family)


def save_reports(path: str, reports):
    with open(path, "w") as f:
        json.dump([r.to_json() for r in reports], f, indent=1)


def load_reports(path: str):
    with open(path) as f:
        return json.load(f)
