"""Share of its roofline that the general price search reaches on the
device, in %: the time the search's operations and bytes need at the chip's
published peaks (``bench/peaks.json``), over the device seconds of its
program in the traced window.

The work is counted from the padded shape and the iteration count alone,
so the count holds whatever implements the search. Per iteration, on a
``(G, k)`` instance padded to the power-of-two bucket of the tenant count:

- operations: ``(LINE_STEPS + 1) * CENTER_ITERS`` centring passes over the
  ``G x k`` price ratios at ``CENTER_FLOPS`` each, plus ``2 G k^2`` for the
  reduced Hessian and ``STEP_FLOPS`` per entry for the gradient, the step
  and the line-search slopes;
- bytes: the instance (``1 / W`` and the row counts, float64) read once.
"""
import jax

from bench.catalog import load_peaks

PROGRAM = "jit__search_segment"
LINE_STEPS = 12
CENTER_ITERS = 16
CENTER_FLOPS = 6
STEP_FLOPS = 16
BYTES = 8


def bucket(n: int) -> int:
    return 8 if n <= 8 else 1 << (n - 1).bit_length()


def work(n: int, k: int, iters: int):
    """(operations, bytes) of ``iters`` search iterations for ``n`` tenants
    on ``k`` types."""
    G = bucket(n)
    per_iter = (G * k * ((LINE_STEPS + 1) * CENTER_ITERS * CENTER_FLOPS
                         + STEP_FLOPS * (LINE_STEPS + 1)) + 2 * G * k * k)
    return per_iter * iters, (G * k + G) * BYTES * iters


def read(ctx):
    if ctx.device is None:
        return None
    seconds = sum(s for name, s in ctx.device.ops if name == PROGRAM)
    recs = [d for d in ctx.decisions if d.record is not None
            and getattr(d.record, "search_iters", 0)]
    if seconds <= 0 or not recs:
        return None
    peaks = load_peaks(jax.devices()[0].device_kind)
    ideal = 0.0
    for d in recs:
        ops, nbytes = work(len(d.tenants), len(d.X[0]), d.record.search_iters)
        ideal += max(ops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * ideal / seconds
