"""Share of prefill rows that are padding: 100 x (1 - prompt_tokens /
prefill_slots) from the args of the last ``serve.round_commit`` in the
traced window, which carry ``SchedulerCore.counts`` over the serve so far
(every prompt the window admitted until then). None when the program
writes no such spans."""

from bench import spans


def read(run):
    c = spans.last_commit(spans.of(run), run.trace.t0, run.trace.t1)
    if not c or not c.get("prefill_slots"):
        return None
    return 100.0 * (1.0 - c["prompt_tokens"] / c["prefill_slots"])
