"""Share of the window's sampling step dispatches whose batch held no
sampled row, so that ``ops.sample_tokens`` took its argmax-only branch
(%). The program's ``sampler_argmax_calls`` counter over its
``sampler_calls`` counter."""

COUNTERS = ("sampler_calls", "sampler_argmax_calls")


def read(run):
    if any(c not in run.after for c in COUNTERS) \
            or run.delta("sampler_calls") <= 0:
        return None
    return 100.0 * run.delta("sampler_argmax_calls") / \
        run.delta("sampler_calls")
