"""How late the open-loop generator sent its requests after they were
due, as a 95th percentile (client layer)."""

from bench import stats


def read(ctx):
    late = ctx["counts"]["client_late_s"]
    if not late:
        return None
    return 1e3 * stats.percentile(late, 95)
