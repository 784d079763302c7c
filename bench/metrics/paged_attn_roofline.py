"""Percent of its roofline that the paged-attention Pallas kernel
(``kernels/paged_attention.py::paged_mixed_attention``) reached in the
window: for every call, the least time the chip could take -- the larger
of its operations over the bf16 peak and its bytes over the HBM
bandwidth (``bench/counts.py``, from each mixed step's positions and
query counts) -- summed and divided by the kernel's time in the device
trace.  One call per paged layer per mixed step."""

import counts
import trace_reduce


def is_kernel(event) -> bool:
    """The kernel's events in the device trace.  The Pallas call carries
    no name of its own, so the trace shows it as the custom call
    "%paged_mixed_attention.<n> = ...", after its jitted wrapper; an
    operation that only takes its result as an operand is not it."""
    return trace_reduce.short_name(event.name).startswith(
        "paged_mixed_attention")


def read(run):
    if run.trace_events is None or run.peaks is None or not run.ticks:
        return None
    seconds, calls = trace_reduce.kernel_time(
        run.trace_events, window_span="bench.window", kernel=is_kernel)
    if not calls:
        return None
    least = 0.0
    for poss, q_lens in run.ticks:
        flops, nbytes = counts.paged_attention_work(
            run.model, poss, q_lens, run.page_size)
        least += max(flops / run.peaks["flops_bf16"],
                     nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * run.model["num_layers"] / seconds
