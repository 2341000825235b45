"""Two-process probe of JAX's persistent compilation cache on the attached chip.

    python -m chipbench.probe_cache            # parent: starts the children, never imports jax
    python -m chipbench.probe_cache --child N  # one child: compiles, reports hits

PR 22 saw 1 persistent-cache hit of 308 across two processes on the chip and 206
of 206 on the CPU backend. Every run of every cell is a new process, so whether
the second process finds the first one's programs decides most of `setup_s`.
The children compile (a) one small jitted program and (b) one tiny engine query,
with the cache-key components logged, into the same fixed cache directory. The
parent prints what differed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import logging
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def child(n: int, cache_dir: str) -> int:
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    if n != 3:   # child 3 keeps JAX's default thresholds, as the engine does
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_explain_cache_misses", True)
    for name in ("jax._src.cache_key", "jax._src.compilation_cache",
                 "jax._src.compiler"):
        lg = logging.getLogger(name)
        lg.setLevel(logging.DEBUG)
        h = logging.StreamHandler(sys.stdout)
        h.setFormatter(logging.Formatter(f"[child {n} %(name)s] %(message)s"))
        lg.addHandler(h)
    counts = {"requests": 0, "hits": 0, "compiles": 0}

    def evt(event, **_kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1

    def dur(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["compiles"] += 1

    jax.monitoring.register_event_listener(evt)
    jax.monitoring.register_event_duration_secs_listener(dur)
    import jax.numpy as jnp
    d = jax.devices()[0]
    print(f"[child {n}] device {d.platform} {d.device_kind} x{len(jax.devices())}",
          flush=True)

    @jax.jit
    def small(x):
        return jnp.cumsum(x * 2.0 + 1.0).sum()

    small(jnp.arange(4096, dtype=jnp.float32)).block_until_ready()
    print(f"[child {n}] small program: {counts}", flush=True)
    before = dict(counts)

    import numpy as np
    import pyarrow as pa
    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F
    rng = np.random.default_rng(7)
    t = pa.table({"a": rng.random(1 << 14), "b": rng.random(1 << 14),
                  "k": rng.integers(0, 4, 1 << 14).astype(np.int32)})
    s = TpuSession({"spark.rapids.sql.enabled": "true"})
    df = s.createDataFrame(t).device_cache()
    out = (df.filter(F.col("a") < 0.5).groupBy("k")
           .agg(F.sum(F.col("a") * F.col("b")).alias("r")).sort("k").collect())
    s.stop()
    print(f"[child {n}] engine query ({len(out)} rows): "
          f"{ {k: counts[k] - before[k] for k in counts} }", flush=True)
    print(f"[child {n}] TOTAL {counts}", flush=True)
    return 0


def parent() -> int:
    cache_dir = os.path.join(ROOT, ".jax_cache_probe")
    print("environment:", {k: v for k, v in sorted(os.environ.items())
                           if re.match(r"(JAX|XLA|TPU|LIBTPU|PJRT)", k)}, flush=True)
    outs = []
    for n in (1, 2, 3):
        env = dict(os.environ)
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        p = subprocess.run([sys.executable, "-m", "chipbench.probe_cache",
                            "--child", str(n), "--cache-dir", cache_dir],
                           cwd=ROOT, env=env, capture_output=True, text=True,
                           timeout=1500)
        outs.append(p.stdout)
        tail = p.stderr[-600:]
        print(f"--- child {n} rc={p.returncode}; stderr tail:\n{tail}", flush=True)
        names = sorted(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else []
        print(f"--- cache dir after child {n}: {len(names)} entries", flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for i, o in enumerate(outs, 1):
        with open(os.path.join(out_dir, f"probe_child{i}.log"), "w") as f:
            f.write(o)
    # per program, in order: the hash of each key component, child 1 against child 2
    comp = [re.findall(r"hash of serialized ([\w ]+): (\w+)", o) for o in outs]
    differ = {}
    for (n1, h1), (n2, h2) in zip(comp[0], comp[1]):
        if n1 == n2 and h1 != h2:
            differ[n1] = differ.get(n1, 0) + 1
    print(f"key components hashed: {len(comp[0])} / {len(comp[1])}; components whose "
          f"hash differs between the processes (count of programs): {differ}")
    for o in outs:
        for ln in o.splitlines():
            if re.match(r"\[child \d\] ", ln):
                print(ln)
        hits = len(re.findall(r"Persistent compilation cache hit", o))
        miss = len(re.findall(r"PERSISTENT COMPILATION CACHE MISS", o))
        notw = re.findall(r"Not writing.*", o)
        print(f"   log: {hits} hits, {miss} misses, {len(notw)} not written "
              f"{notw[:2]}")
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, default=0)
    ap.add_argument("--cache-dir", default="")
    a = ap.parse_args()
    sys.exit(child(a.child, a.cache_dir) if a.child else parent())
