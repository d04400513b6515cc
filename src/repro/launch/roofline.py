"""Three-term roofline analysis from the compiled dry-run artifact.

Device peaks come from ``PEAKS``, keyed by ``jax.Device.device_kind``; the
dry-run plans for ``TARGET_DEVICE_KIND`` (TPU v5e) whatever it runs on.

Terms (per assignment):
    compute_s    = HLO_FLOPs / peak            (cost_analysis is per-device
                                                for an SPMD executable)
    memory_s     = HLO_bytes / HBM_bw
    collective_s = collective_bytes / link_bw  (parsed from the partitioned
                                                HLO text — per-device shapes)

``MODEL_FLOPS`` bookkeeping uses 6*N*D (dense) / 6*N_active*D (MoE) for train
and 2*N*D for inference, so the ``useful-flops ratio`` exposes remat /
dispatch-overhead waste in the compiled module.
"""
from __future__ import annotations

import dataclasses
import json
import re



@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops: float        # bf16 FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link
    hbm_bytes: int      # HBM capacity per chip


# Published per-chip peaks by ``device_kind``.  TPU v5e: Google Cloud
# documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s and 16 GB of HBM,
# 1,600 Gbit/s of interconnect over 4 links (50 GB/s each).  JAX reports a
# v5e as "TPU v5 lite".
_V5E = DevicePeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                   hbm_bytes=16 * 1024**3)
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")
_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"%([\w.-]+) = \(?(\w+)\[([0-9,]*)\]")
_OPND_RE = re.compile(r"%([\w.-]+)")
# ops whose operands/results actually stream HBM on TPU (elementwise chains
# fuse into these); used for the fusion-aware memory proxy.
_HBM_OPS = (" dot(", " convolution(", " gather(", " scatter(", " sort(",
            " dynamic-update-slice(", " reduce(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective instruction, by op kind.

    In the SPMD-partitioned module shapes are per-device; async pairs are
    counted once (the ``-start`` op).  For all-reduce / all-to-all /
    collective-permute the result size equals the operand size; for
    all-gather it is the post-gather size and for reduce-scatter the
    pre-reduce size is result * group — we report result bytes (the wire
    traffic of ring algorithms is within 2x of this; constants noted in
    EXPERIMENTS.md).
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for op in _COLLECTIVES:
            # match `op(`, `op-start(` but not `-done(`
            if f" {op}(" in stripped or f" {op}-start(" in stripped:
                lhs = stripped.split(f" {op}", 1)[0]
                total = sum(_shape_bytes(d, dims)
                            for d, dims in _SHAPE_RE.findall(lhs))
                out[op] = out.get(op, 0) + total
                break
    return out


def fused_bytes(hlo_text: str, arg_bytes: float, out_bytes: float) -> float:
    """Fusion-aware HBM-traffic proxy for the TPU target.

    The CPU backend's ``bytes accessed`` counts every unfused intermediate
    (20-30x what a TPU module would stream).  TPU fuses elementwise chains
    into their matmul/reduce producers, so we approximate HBM traffic as
    (operands + result) of dot/conv/gather/scatter/sort/reduce instructions
    plus one read of the entry arguments and one write of the outputs.
    Reported next to the raw value; the raw value is the upper bound.
    """
    shape_of: dict[str, int] = {}
    for m in _DEF_RE.finditer(hlo_text):
        shape_of[m.group(1)] = _shape_bytes(m.group(2), m.group(3))
    total = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if not any(op in s for op in _HBM_OPS):
            continue
        md = _DEF_RE.search(s)
        if md:
            total += _shape_bytes(md.group(2), md.group(3))   # result
        # operand reads (names resolved via the def map)
        args = s.split("(", 2)
        if len(args) >= 2:
            for om in _OPND_RE.finditer(args[-1].split(")", 1)[0]):
                total += shape_of.get(om.group(1), 0)
    return float(total) + arg_bytes + out_bytes


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    strategy: str
    n_devices: int
    flops_per_dev: float
    bytes_per_dev: float           # fusion-aware proxy (see fused_bytes)
    bytes_per_dev_raw: float       # CPU-backend 'bytes accessed' (upper bound)
    coll_bytes_per_dev: float
    coll_breakdown: dict
    peak_mem_per_dev: float        # CPU buffer-assignment temp (pessimistic:
                                   # CPU liveness != TPU; see EXPERIMENTS.md)
    arg_bytes_per_dev: float
    act_bytes_est: float = 0.0     # analytic activation estimate (TPU model)
    model_flops_global: float = 0.0
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    useful_ratio: float = 0.0
    fits_hbm: bool = True
    step_s: float = 0.0
    roofline_frac: float = 0.0
    device_kind: str = TARGET_DEVICE_KIND

    def finalize(self):
        pk = peaks(self.device_kind)
        self.compute_s = self.flops_per_dev / pk.flops
        self.memory_s = self.bytes_per_dev / pk.hbm_bw
        self.collective_s = self.coll_bytes_per_dev / pk.ici_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.dominant = max(terms, key=terms.get)
        hlo_global = self.flops_per_dev * self.n_devices
        self.useful_ratio = (self.model_flops_global / hlo_global
                             if hlo_global else 0.0)
        # fit decided on args + analytic activations: CPU temp is an artifact
        # of CPU buffer liveness, reported but not used for the verdict.
        self.fits_hbm = (self.act_bytes_est
                         + self.arg_bytes_per_dev) <= pk.hbm_bytes
        # overlap model: compute overlaps with memory AND collectives at best
        self.step_s = max(terms.values())
        ideal_s = self.model_flops_global / (self.n_devices * pk.flops)
        self.roofline_frac = ideal_s / self.step_s if self.step_s else 0.0
        return self

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def act_bytes_estimate(cfg, shape_name: str, shapes: dict, n_data_shards: int) -> float:
    """Per-device activation memory under the TPU deployment model:
    bf16 remat residual stash (one checkpoint per layer) for train, an
    8x-residual transient for prefill, negligible for decode."""
    sh = shapes[shape_name]
    tokens_dev = sh["global_batch"] * sh["seq_len"] / n_data_shards
    resid = tokens_dev * cfg.d_model * 2
    if sh["step"] == "train":
        return float(cfg.n_layers * resid + 8 * resid)
    if sh["step"] == "prefill":
        return float(8 * resid)
    return float(2 * cfg.d_model * sh["global_batch"] * 8)


def analyze(compiled, *, arch: str, shape: str, mesh, strategy: str,
            model_flops_global: float, hlo_text: str | None = None,
            act_bytes: float = 0.0) -> Roofline:
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    arg_b = float(mem.argument_size_in_bytes)
    out_b = float(mem.output_size_in_bytes)
    r = Roofline(
        arch=arch, shape=shape, mesh="x".join(map(str, mesh.shape.values())),
        strategy=strategy, n_devices=n_dev,
        flops_per_dev=float(cost.get("flops", 0.0)),
        bytes_per_dev=fused_bytes(text, arg_b, out_b),
        bytes_per_dev_raw=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_dev=float(sum(coll.values())),
        coll_breakdown=coll,
        peak_mem_per_dev=float(mem.temp_size_in_bytes + mem.output_size_in_bytes),
        arg_bytes_per_dev=arg_b,
        act_bytes_est=act_bytes,
        model_flops_global=model_flops_global,
    )
    return r.finalize()


def model_flops(cfg, shape_name: str, shapes: dict) -> float:
    """6*N_active*tokens for train, 2*N_active*tokens for inference."""
    sh = shapes[shape_name]
    n = cfg.active_param_count()
    if sh["step"] == "train":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 6.0 * n * tokens
    if sh["step"] == "prefill":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 2.0 * n * tokens
    tokens = sh["global_batch"]  # one new token per sequence
    return 2.0 * n * tokens


def save_record(rec: Roofline, path: str) -> None:
    with open(path, "w") as f:
        json.dump(rec.to_json(), f, indent=2)
