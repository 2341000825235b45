"""Records a small device trace for chipbench's self-test: a handful of launches of two tiny
programs inside the harness's window span, with one deliberate idle gap under a named host span."""
import os, shutil, sys, time
import jax, jax.numpy as jnp, jax.profiler as jp
sys.path.insert(0, os.getcwd())
from chipbench import trace as tm
out = "chiprun_out/small_trace"
shutil.rmtree(out, ignore_errors=True)
f = jax.jit(lambda x: jnp.cumsum(x * 2.0).sum())
g = jax.jit(lambda x: (x @ x).sum())
x = jnp.ones((1 << 16,), jnp.float32); y = jnp.ones((256, 256), jnp.float32)
f(x).block_until_ready(); g(y).block_until_ready()
o = jp.ProfileOptions(); o.python_tracer_level = 0; o.host_tracer_level = 2
jp.start_trace(out, profiler_options=o)
with jp.TraceAnnotation(tm.WINDOW_SPAN):
    for i in range(3):
        with jp.TraceAnnotation(tm.QUERY_SPAN + " a"):
            f(x).block_until_ready()
    with jp.TraceAnnotation("host.sleep"):
        time.sleep(0.05)
    for i in range(2):
        with jp.TraceAnnotation(tm.QUERY_SPAN + " b"):
            g(y).block_until_ready()
jp.stop_trace()
p = tm.find_xplane(out)
print("xplane", p, os.path.getsize(p))
lines = tm.load(p)
print(tm.describe(lines, 8))
print(tm.reduce(lines))
shutil.copy(p, "chiprun_out/small.xplane.pb")
