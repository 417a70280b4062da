#!/usr/bin/env python3
"""Record ``data/scoped.xplane.pb`` and ``data/scoped.spans.json`` on one TPU
chip, for ``test_program_trace.py``:

    python3 bench/tests/record_scoped_trace.py [out_dir]

A tiny jitted function with two named scopes (``probe_dense``: a matmul and
tanh; ``probe_kernel``: the program's masked-matmul kernel, named
``masked_matmul_fwd``) runs three times.  Each call sits inside a
``repro.obs`` span and a ``jax.profiler.TraceAnnotation`` of the same name
(``probe.compute``), followed by a 5 ms ``probe.sleep`` pair; the host
tracer records the annotations, the ring the spans.  The spans are written
next to the trace as JSON, so the test can place them by the trace's
``profile_start_time`` and compare them with the annotations.

It also prints the host cost of a span into the ring, in ns.
"""
import glob
import json
import os
import shutil
import sys
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from repro import obs
    from repro.kernels.masked_matmul import masked_matmul

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace.py needs a TPU chip", file=sys.stderr)
        return 2

    @jax.jit
    def probe(x, w, alive):
        with jax.named_scope("probe_dense"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("probe_kernel"):
            return masked_matmul(h, w, alive, name="masked_matmul_fwd")

    x = jnp.ones((256, 512), jnp.float32) / 512
    w = jnp.ones((512, 512), jnp.float32) / 512
    alive = jnp.asarray([1, 0, 1, 1], jnp.int32)
    probe(x, w, alive).block_until_ready()

    tmp = os.path.join(out, "trace")
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    t_start = time.time_ns()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for i in range(3):
        with obs.span("probe.compute", round=i), \
                jax.profiler.TraceAnnotation("probe.compute"):
            probe(x, w, alive).block_until_ready()
        with obs.span("probe.sleep", round=i), \
                jax.profiler.TraceAnnotation("probe.sleep"):
            time.sleep(0.005)
    jax.profiler.stop_trace()

    spans = [s._asdict() for s in obs.recent_spans()
             if s.name.startswith("probe.") and s.start_ns >= t_start]
    trace = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True), key=os.path.getmtime)
    shutil.copy(trace, os.path.join(out, "scoped.xplane.pb"))
    with open(os.path.join(out, "scoped.spans.json"), "w") as f:
        json.dump(spans, f, indent=1)
        f.write("\n")

    rec = obs.Recorder(armed=False)
    n = 200_000
    ring = timeit.timeit(lambda: obs.span("x").__enter__().__exit__(),
                         number=n) / n
    with_rec = timeit.timeit(
        lambda: rec.span("x", round=1).__enter__().__exit__(), number=n) / n
    print(json.dumps({"spans": len(spans),
                      "trace_bytes": os.path.getsize(trace),
                      "ns_per_span": ring * 1e9,
                      "ns_per_recorder_span": with_rec * 1e9}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(ROOT, ".bench_out", "scoped")))
