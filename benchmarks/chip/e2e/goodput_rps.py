"""Requests due in the window that finished within both limits of their
tier, over the window, req/s. One that failed or did not finish misses."""
import stats


def read(s):
    tiers = {n: stats.Tier(n, t["ttft_ms"], t["tpot_ms"]) for n, t in s.tiers.items()}
    return stats.goodput_rps(s.records, tiers, s.seconds)
