"""Mean blocking read behind a prefill call (``ServingMetrics``:
``prefill_read_wait_s`` over ``prefill_reads``): how long the loop thread,
and with it every decoding lane's next dispatch, stands behind a prefill
program. GPT-2's family reads twice an admitted batch (the first tokens,
which waits out the program, and the settle behind the lane installs); the
families over state slots read once a call that ends a prompt."""


def read(run):
    reads = run.counters.get("prefill_reads", 0)
    if not reads or "prefill_read_wait_s" not in run.counters:
        return None
    return 1e3 * run.counters["prefill_read_wait_s"] / reads
