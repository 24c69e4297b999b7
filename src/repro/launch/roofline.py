"""Roofline-term derivation from compiled dry-run artifacts (§Roofline).

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs/bytes come from our trip-count-weighted HLO analyzer
(core/hlo_analysis.py) because XLA's ``cost_analysis()`` counts while bodies
once (verified; see that module's docstring) — we report both so the
correction factor is visible. Collective bytes are parsed from the
post-optimization HLO with standard per-op accounting. All quantities are
per-device (the compiled module is one SPMD participant), so dividing by the
per-chip peaks directly yields the cell's step-time lower bound.

Hardware constants (task spec): 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link
ICI per chip.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass

from ..core.devices import ROOFLINE_HBM_BW, ROOFLINE_ICI_BW, ROOFLINE_PEAK_FLOPS
from ..core.hlo_analysis import analyze_hlo_text

HBM_PER_CHIP = 16 * 2**30      # v5e


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    n_devices: int
    strategy: str
    # per-device, trip-count corrected
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    collective_breakdown: dict
    # raw cost_analysis numbers (loop bodies counted once) for comparison
    xla_flops: float
    xla_bytes: float
    # memory_analysis
    arg_bytes: int
    out_bytes: int
    temp_bytes: int
    peak_bytes: int
    fits_hbm: bool
    # XLA:CPU emulates bf16 dots by upconverting operands to f32; when the
    # operand is a stacked bf16 cache/param the hoisted convert materializes
    # an f32 copy that does NOT exist on TPU (native bf16 MXU). We measure
    # those buffers and report the TPU-adjusted peak alongside the raw one.
    cpu_upcast_bytes: int = 0
    peak_bytes_tpu: int = 0
    fits_hbm_tpu: bool = True
    # terms (seconds)
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    dominant: str = ""
    # usefulness
    model_flops: float = 0.0          # 6ND / 2ND analytic, GLOBAL
    useful_ratio: float = 0.0         # model_flops / (hlo_flops * chips)
    roofline_frac: float = 0.0        # t_ideal_compute / t_bound
    note: str = ""

    def finalize(self):
        self.t_compute = self.hlo_flops / ROOFLINE_PEAK_FLOPS
        self.t_memory = self.hlo_bytes / ROOFLINE_HBM_BW
        self.t_collective = self.collective_bytes / ROOFLINE_ICI_BW
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        self.dominant = max(terms, key=terms.get)
        total_hlo_flops = self.hlo_flops * self.n_devices
        self.useful_ratio = (self.model_flops / total_hlo_flops
                             if total_hlo_flops else 0.0)
        # fraction of the compute roofline actually achievable given the
        # dominating term: t_useful_compute / max(all terms)
        t_useful = (self.model_flops / self.n_devices) / ROOFLINE_PEAK_FLOPS
        bound = max(terms.values())
        self.roofline_frac = t_useful / bound if bound > 0 else 0.0
        return self

    def row(self) -> str:
        return (f"{self.arch},{self.shape},{self.mesh},{self.strategy},"
                f"{self.t_compute*1e3:.2f}ms,{self.t_memory*1e3:.2f}ms,"
                f"{self.t_collective*1e3:.2f}ms,{self.dominant},"
                f"useful={self.useful_ratio:.2f},roofline={self.roofline_frac:.2f},"
                f"mem={self.peak_bytes/2**30:.1f}GiB,fits={self.fits_hbm},"
                f"mem_tpu={self.peak_bytes_tpu/2**30:.1f}GiB,"
                f"fits_tpu={self.fits_hbm_tpu}")


def cpu_upcast_bytes(hlo_text: str, min_bytes: int = 2**28) -> int:
    """Bytes of large f32 buffers produced by pure dtype CONVERTS (bf16->f32
    dot-operand emulation on XLA:CPU; absent on TPU where the MXU consumes
    bf16 natively). Counted once per instruction, skipping fusion-internal
    bodies (they alias the fusion's output buffer)."""
    from ..core.hlo_analysis import parse_hlo_computations
    comps = parse_hlo_computations(hlo_text)
    total = 0
    for comp in comps.values():
        if comp.name.startswith(("wrapped_convert_computation",
                                 "fused_computation")):
            continue
        for instr in comp.instrs:
            if not instr.result_type.startswith("f32"):
                continue
            is_conv = (instr.op == "convert"
                       or (instr.op == "fusion"
                           and "wrapped_convert" in instr.rest))
            if not is_conv:
                continue
            b = instr.result_bytes
            if b >= min_bytes:
                total += int(b)
    return total


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train (fwd+bwd), 2·N·D forward-only.
    MoE uses active params. D = tokens processed by the step."""
    n = cfg.params_active()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch          # decode: 1 token per seq


def analyze_cell(compiled, *, arch: str, shape, mesh_name: str,
                 n_devices: int, strategy: str, cfg) -> RooflineReport:
    txt = compiled.as_text()
    bf16 = getattr(cfg, "dtype", "") == "bfloat16"
    costs = analyze_hlo_text(txt, n_devices=n_devices, logical_bf16=bf16)
    ca = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    arg_b = int(getattr(mem, "argument_size_in_bytes", 0))
    out_b = int(getattr(mem, "output_size_in_bytes", 0))
    tmp_b = int(getattr(mem, "temp_size_in_bytes", 0))
    alias_b = int(getattr(mem, "alias_size_in_bytes", 0))
    peak = arg_b + tmp_b + out_b - alias_b
    upcast = cpu_upcast_bytes(txt)
    peak_tpu = max(peak - upcast, arg_b)
    rep = RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, n_devices=n_devices,
        strategy=strategy,
        hlo_flops=costs.flops, hlo_bytes=costs.hbm_bytes,
        collective_bytes=costs.collective_bytes,
        collective_breakdown=dict(costs.collective_bytes_by_op),
        xla_flops=float(ca.get("flops", 0.0)),
        xla_bytes=float(ca.get("bytes accessed", 0.0)),
        arg_bytes=arg_b, out_bytes=out_b, temp_bytes=tmp_b, peak_bytes=peak,
        fits_hbm=peak <= HBM_PER_CHIP,
        cpu_upcast_bytes=upcast,
        peak_bytes_tpu=peak_tpu,
        fits_hbm_tpu=peak_tpu <= HBM_PER_CHIP,
        model_flops=model_flops_for(cfg, shape),
    )
    return rep.finalize()


def save_report(rep: RooflineReport, path) -> None:
    from pathlib import Path
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(asdict(rep), f, indent=1)
