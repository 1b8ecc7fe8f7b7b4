"""Single source of the hardware constants, keyed by ``device_kind``.

Every analytic performance model in the repo reads this table — the LLM
roofline (``benchmarks/roofline.py``), the mesh/dry-run plane
(``repro.launch.mesh`` re-exports ``HW`` unchanged), and the kernel cost
model (``repro.analysis.kernel_audit``). Two models quoting different peak
numbers would make their "fraction of roofline" columns incomparable, so
the constants live in exactly one place and a test pins every consumer to
the same object.

A device that is not in the table is an error (:func:`chip`), never a
default: a peak borrowed from another chip would make every ratio built on
it wrong without a trace.
"""
from __future__ import annotations

#: Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
#: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s
#: of inter-chip interconnect (four links).
CHIPS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,     # FLOP/s
        "peak_ops_int8": 393e12,       # OP/s
        "hbm_bandwidth": 819e9,        # B/s
        "ici_bandwidth": 50e9,         # B/s per link (1,600 Gbit/s / 4)
        "hbm_bytes": 16 * 2**30,
        # the kernels' VMEM target: the default scoped-VMEM limit per core.
        # Kernels budget against a fraction of it (pipeline buffers and
        # compiler scratch need headroom); the compiler itself reports
        # 128 MiB of physical VMEM on this chip.
        "vmem_bytes": 16 * 1024 * 1024,
    },
}

#: The chip the kernels and the analytic models are written for.
TARGET_KIND = "TPU v5 lite"


def chip(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises for a kind not listed."""
    try:
        return CHIPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no hardware table for device kind {device_kind!r}; known: "
            f"{sorted(CHIPS)}") from None


HW = chip(TARGET_KIND)
