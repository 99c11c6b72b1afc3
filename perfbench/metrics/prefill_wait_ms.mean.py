"""Mean milliseconds from a request's submit to the dispatch of the first
chunk holding its prompt: admission, then waiting behind other prompts'
chunks.  The program's ``prefill_wait_s`` counter over its
``first_tokens`` counter, both added to at each request's first token;
with ``prefill_ms.mean`` it makes up the mean time to first token from
submit."""

COUNTER = "prefill_wait_s"


def read(run):
    if COUNTER not in run.after or run.delta("first_tokens") <= 0:
        return None
    return 1e3 * run.delta(COUNTER) / run.delta("first_tokens")
