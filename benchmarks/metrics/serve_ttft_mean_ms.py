"""Mean time to first token, from the instant a request was due."""


def read(run):
    xs = run.host.get("ttft_ms") or []
    return sum(xs) / len(xs) if xs else None
