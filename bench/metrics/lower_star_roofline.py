"""Share of its HBM roofline that the fused lower-star gradient kernel
reaches, in %.

For each of the kernel's events in the traced window, the least time is
the bytes it must move over the chip's HBM bandwidth: each distinct
operand read once (the halo-padded rank volume and the face table) and
each result written once (the packed status and partner words), from the
shapes in the op's own HLO text.  The share is the summed least time
over the summed device time of those events.  The kernel's operations
(integer VPU work) are not counted, since the peaks table holds no VPU
peak, so this is the HBM bound alone and cannot pass 100%: the kernel
moves at least those bytes.

The kernel is the XLA op named after the program's jitted
``_fused_call`` (``kernels/lower_star.py``).  A trace with device
operations but none of the kernel's is an error, not a silent gap: the
program has renamed or replaced the kernel.
"""

from bench import tracing

KERNEL = "_fused_call"


def read(run):
    if run.trace is None or not run.trace.device_ops:
        return None
    events = run.trace.kernel_events(KERNEL)
    if not events:
        raise LookupError(f"no device event named {KERNEL!r} in the traced "
                          "window: the gradient kernel was renamed or not run")
    moved = sum(tracing.hlo_bytes(run.trace.hlo[name]) for name, _ in events)
    least_s = moved / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / sum(s for _, s in events)
