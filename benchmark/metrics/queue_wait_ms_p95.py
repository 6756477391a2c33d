"""95th percentile of the time a request waited between submit and
admission, from the server's ``autodist_serving_queue_wait_seconds``
histogram (the difference of its buckets over the window; linear inside
the bucket, so bucket resolution)."""


def read(run):
    pair = run.counters.get("queue_wait_hist")
    if not pair:
        return None
    before, after = pair
    bounds = sorted(after)
    cum = [after[b] - before.get(b, 0.0) for b in bounds]
    total = cum[-1] if cum else 0.0
    if total <= 0:
        return None
    rank, lo, seen = 0.95 * total, 0.0, 0.0
    finite = [b for b in bounds if b != float("inf")]
    for b, c in zip(bounds, cum):
        if c >= rank:
            if b == float("inf"):
                return finite[-1] * 1e3
            inside = c - seen
            frac = (rank - seen) / inside if inside else 1.0
            return (lo + (b - lo) * frac) * 1e3
        seen, lo = c, b
    return finite[-1] * 1e3
