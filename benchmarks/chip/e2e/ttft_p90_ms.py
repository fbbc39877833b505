"""90th percentile of time to first token over the requests due in the
window, from each one's due time, ms."""
import stats


def read(s):
    return stats.percentile([r.ttft_ms(s.end_s) for r in s.records], 90)
