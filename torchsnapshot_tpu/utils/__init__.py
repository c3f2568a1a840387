from .rss_profiler import measure_rss_deltas, RSSDeltas

__all__ = ["measure_rss_deltas", "RSSDeltas"]
