"""Adahessian update kernel (``repro/kernels/adahessian``): device time of its
``adahessian_update_batched`` events in the traced window per round, averaged
over the chips that ran it. No value where the kernel did not run (sharded
placement takes the jnp path).

A share of the HBM roofline is not reported: XLA stages the kernel's
operands through VMEM with asynchronous copies outside the kernel's event
(the trace shows its operands and outputs in memory space S(1)), so its
least bytes over HBM bandwidth read above 100% of the event's time."""
from bench import trace

KERNEL = "adahessian_update_batched"


def read(run):
    return trace.kernel_ms_per_round(run.trace.devices, KERNEL,
                                     run.window_ns, run.rounds)
