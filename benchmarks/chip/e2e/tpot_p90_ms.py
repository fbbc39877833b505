"""90th percentile over the requests due in the window of
(last token - first token) / (tokens - 1), ms."""
import stats


def read(s):
    return stats.percentile([r.tpot_ms for r in s.records if r.tpot_ms is not None], 90)
