"""The longest gap between two output tokens of one request."""


def read(run):
    xs = run.host.get("gaps_ms") or []
    return max(xs) if xs else None
