"""Percent of the chip's bf16 peak that the model's work took over the
window: the forward operations of every token fed (prompt tokens and
every served token but each request's last, counted by
``bench/counts.py`` with the binarised MLP as its dense GEMM), over the
window's host-clock seconds and the peak in ``bench/peaks.json``."""

import counts


def read(run):
    if run.peaks is None or not run.requests:
        return None
    flops = sum(counts.model_flops(run.model,
                                   counts.request_positions(p, n))
                for p, n, _, _ in run.requests)
    return 100.0 * flops / run.window_s / run.peaks["flops_bf16"]
