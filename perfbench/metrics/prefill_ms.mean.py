"""Mean milliseconds from the dispatch of the first chunk holding a
request's prompt to its first token: its own prefill service.  The
program's ``prefill_s`` counter over its ``first_tokens`` counter, both
added to at each request's first token; with ``prefill_wait_ms.mean`` it
makes up the mean time to first token from submit."""

COUNTER = "prefill_s"


def read(run):
    if COUNTER not in run.after or run.delta("first_tokens") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("first_tokens")
