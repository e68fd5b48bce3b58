"""Entry points: how late the benchmark's own generator sent a request, sent
minus due, 99th percentile over the requests due in the window.  It guards
``ttft_p90_ms``: a late generator is not a fast server."""

from benchmark import stats


def read(obs):
    return stats.percentile(
        [(r["sent"] - r["due"]) * 1e3 for r in obs["requests"]
         if stats.in_window(obs, r["due"]) and r["sent"]], 99)
