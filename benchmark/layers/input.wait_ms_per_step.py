"""Host milliseconds a step waited for its batch (`bench.next_batch`).
The pool is made during set-up, so this is the cost of handing over a
ready batch; a cell with a real loader would wait here."""


def read(run):
    w = run.window
    return run.spans.total_ms("bench.next_batch", w.start_ns, w.end_ns) \
        / w.steps
