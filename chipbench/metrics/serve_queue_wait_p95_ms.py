"""95th percentile, over the window's requests, of the time from the
submit call to the return of the first step whose ``admission_log`` holds
the request (harness clock): the admission path of this engine, which is
the packer thread's prefill and staging, the hand-off of its arrival plan,
and the wait in the admission queue for a decode slot."""
UNIT = "ms"
LAYER = "admission"
MOVES = "serve_ttft_mean_ms"


def read(r):
    return r.counters.get("queue_wait_p95_ms")
