"""The quantizer kernel's share of its HBM roofline: the bytes the
algorithm needs (each message's float32 plane read, its codes written;
``bench/flops.py``) over the summed device time of the kernel's events
and the chip's HBM bandwidth."""

from bench import flops

# the Pallas call shows in the trace as a custom call named after the
# jitted function that launches it (kernels/quantize/kernel.py)
KERNEL = "quantize_rows"


def read(ctx):
    sec = ctx.reduction.kernel_s(KERNEL) if ctx.reduction else 0.0
    if sec <= 0:
        return None
    moved = flops.quantize_bytes_per_round(ctx.model, ctx.train) * ctx.rounds
    return moved / sec / ctx.peaks["hbm_bytes_per_s"] * 100.0
