"""Host milliseconds per round inside batch sampling: the ``bench.sample``
spans that ``run.HostSpans`` times around the engine's sampler."""


def read(ctx):
    lo, hi = ctx["lo"], ctx["hi"]
    tot = sum(min(e, hi) - max(s, lo) for n, s, e in ctx["trace"].spans
              if n == "bench.sample" and e > lo and s < hi)
    if not tot:
        return None
    return tot * 1e-6 / ctx["rounds"]
