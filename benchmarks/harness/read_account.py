"""The serving loop's account of its plain decode reads, as the readers of it
take it (``ServingMetrics``: ``decode_reads_le_us_<edge>`` counts and
``decode_read_s_le_us_<edge>`` seconds of the blocking reads of a decode
step's tokens that had no prefill program inside their wait, by the read's
length: upper edges 250 us doubling to 512 ms, and ``inf``)."""

_COUNT, _SECONDS = "decode_reads_le_us_", "decode_read_s_le_us_"


def buckets(counters):
    """``[(upper edge in us, reads, seconds)]`` in ascending order; None
    where the program counts no such reads (the parent of the PR that
    brought them) or the window held none."""
    rows = []
    for key, reads in counters.items():
        if key.startswith(_COUNT):
            edge = key[len(_COUNT):]
            if _SECONDS + edge not in counters:
                return None
            rows.append((float(edge), reads, counters[_SECONDS + edge]))
    if not any(reads for _, reads, _ in rows):
        return None
    return sorted(rows)


def late_from_us(rows):
    """The length in us from which a read of ``rows`` is late: twice the
    upper edge of the bucket that holds the median read. A median of 18 ms
    sits in (16, 32], so late is from 64 ms; of 58 ms, in (32, 64], so from
    128 ms. A step's own length never reaches that; a read the host was
    handed late (80-200 ms on steps of 19-25, PERF.md section 6) does. The
    buckets double, so this is between twice and four times the median, and
    it halves or doubles when the median crosses an edge: two runs whose
    medians lie on either side of one (15.9 and 16.1 ms) count late from 32
    and from 64 ms, which is why ``serve_late_read_from_ms`` reports it."""
    total = sum(reads for _, reads, _ in rows)
    seen = 0
    for edge, reads, _ in rows:
        seen += reads
        if 2 * seen >= total:
            return 2 * edge


def split_late(rows):
    """``((reads, seconds) on time, (reads, seconds) late)`` of ``rows``: a
    read is late when the lower edge of its bucket is at least
    ``late_from_us(rows)``."""
    late_from = late_from_us(rows)
    on_time, late, lower = [0, 0.0], [0, 0.0], 0.0
    for edge, reads, seconds in rows:
        side = late if lower >= late_from else on_time
        side[0] += reads
        side[1] += seconds
        lower = edge
    return tuple(on_time), tuple(late)
