"""Record small.xplane.pb, the trace test_trace_reduce.py reads.

    python benchmark/tests/data/fixture.py <output .xplane.pb>

Run once on a TPU: one jitted tanh(x @ x).sum(0) on 512 x 512 f32,
called four times inside a `bench.window` annotation, each call under
`prepare` and its wait under `resolve` (with a 2 ms host sleep).
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
x = jnp.ones((512, 512), jnp.float32)
f(x).block_until_ready()
o = jax.profiler.ProfileOptions()
o.python_tracer_level = 0
o.enable_hlo_proto = False
tmp = tempfile.mkdtemp()
jax.profiler.start_trace(tmp, profiler_options=o)
with jax.profiler.TraceAnnotation("bench.window"):
    for i in range(4):
        with jax.profiler.TraceAnnotation("prepare"):
            y = f(x)
        with jax.profiler.TraceAnnotation("resolve"):
            y.block_until_ready()
            time.sleep(0.002)
jax.profiler.stop_trace()
shutil.copy(glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0], sys.argv[1])
shutil.rmtree(tmp)
