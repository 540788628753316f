"""Share of the int4_matmul kernel's roofline: the least time its traced
calls' work could take on the chip, over the device time of those calls.

Each call ``[m, k] @ [k, n]`` (shapes from its HLO instruction in the
trace) does ``2 m k n`` products of 4-bit weights and 8-bit inputs, so the
int8 peak bounds it; its bytes are the packed weights, the per-column
scales and the float32 activations in and out (``bench/flops.py``)."""

from bench import flops
from bench.trace import kernel_calls

KERNEL = "int4_matmul"


def read(ctx):
    """Percent, or None when the trace shows no call with its shapes."""
    least = spent = 0.0
    for secs, shapes in kernel_calls(ctx.trace, KERNEL):
        if len(shapes) < 2 or len(shapes[0][1]) != 2 or \
                len(shapes[1][1]) != 2:
            return None
        (m, n), (_, k) = shapes[0][1], shapes[1][1]
        ops, nbytes = flops.int4_matmul_work(m, k, n)
        least += flops.roofline_seconds(ops, nbytes,
                                        ctx.peaks["int8_ops_per_s"],
                                        ctx.peaks["hbm_bytes_per_s"])
        spent += secs
    return 100.0 * least / spent if spent > 0 else None
