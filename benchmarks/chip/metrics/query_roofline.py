"""The query kernel's share of its roofline, in percent: the least time of
the window's query work (``work.query_bytes``, bound by HBM bandwidth) over
the device time of the ``worp_countsketch_query_batched`` operations."""
import tracing
import work


def read(run):
    t, peak = run["trace"], run["peak"]
    if t is None or peak is None:
        return None
    return work.roofline_pct(run["work"]["query_bytes"], 0.0,
                             tracing.kernel_s(t, "worp_countsketch_query_batched"),
                             peak)
