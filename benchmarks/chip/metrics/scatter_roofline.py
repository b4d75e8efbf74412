"""The scatter kernel's share of its roofline, in percent: the least time of
the window's scatter work (``work.scatter_bytes``, bound by HBM bandwidth) over
the device time of the ``worp_countsketch_scatter_batched`` operations."""
import tracing
import work


def read(run):
    t, peak = run["trace"], run["peak"]
    if t is None or peak is None:
        return None
    return work.roofline_pct(run["work"]["scatter_bytes"], 0.0,
                             tracing.kernel_s(t, "worp_countsketch_scatter_batched"),
                             peak)
