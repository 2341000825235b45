"""What the attached device does with Spark's 64-bit types, plus the two
pieces of process bootstrap around it: where the persistent compile cache
lives, and keeping spawned workers off the chip their parent holds.

TPU v5e ("TPU v5 lite"), JAX 0.9.0 / libtpu 0.0.34, x64 on — asked of the
compiler in the sandbox and run on the chip (CHANGES.md PR 22 has the probe
output):

* BIGINT is exact. XLA's X64 rewriter carries s64/u64 as u32 pairs; add,
  multiply (wrapping), shifts, compares, sort, segment_sum and the
  i64 -> u32x2 bitcast all compile and agree with numpy bit for bit. Join
  keys, the splitmix hash plane and decimal128's int64 limbs therefore run
  the same 64-bit code on the chip as on the CPU backend.
* DOUBLE is carried as a PAIR OF f32 (hi + lo): about 48 significand bits
  and f32's exponent range. A host double is rounded to that pair on upload
  (relative error <= 2^-48; |x| > 3.4e38 becomes inf, |x| < 1.2e-38 loses
  its low part or flushes to 0). Arithmetic, compares, sort and
  segment_sum compile and run at that precision.
* The one thing the compiler refuses is a BIT VIEW OF f64
  (`bitcast_convert_type` f64 -> s64 / u32x2, and what is built on it:
  `jnp.frexp`, `jnp.nextafter`, `x.view(int64)`): "UNIMPLEMENTED: While
  rewriting computation to not contain X64 element types ...". There are
  no IEEE-754 double bits on the device to view.

So the only capability the engine has to ask about is `f64_bit_views()`.
Where it is False, `f64_order_bits` builds the order/equality-preserving
int64 of a DOUBLE key from the two f32 halves instead — exact for every
value the device can hold, never a cast to f32.

Reference analogue: GpuDeviceManager.scala validates device capabilities at
startup (validateGpuArchitecture); here the probe is a one-time AOT compile.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _f64_bit_views_for(backend: str) -> bool:
    fn = jax.jit(lambda x: jax.lax.bitcast_convert_type(x, jnp.int64))
    try:
        fn.lower(jax.ShapeDtypeStruct((8,), jnp.float64)).compile()
    except jax.errors.JaxRuntimeError as e:
        # the X64 rewriter's refusal and nothing else: any other compile
        # failure is a broken installation and must surface
        if "X64" not in str(e):
            raise
        return False
    return True


def f64_bit_views() -> bool:
    """True when the active backend can reinterpret an f64 as integer bits
    (the CPU backend); False on TPU, whose f64 is an f32 pair. Cached per
    backend name."""
    return _f64_bit_views_for(jax.default_backend())


def f32_order_bits(f):
    """Signed-int32 order encoding of an f32 (sign-flipped IEEE bits)."""
    bits = jax.lax.bitcast_convert_type(f, jnp.int32)
    return jnp.where(bits < 0, ~bits ^ jnp.int32(-2**31), bits)


def f64_order_bits(d):
    """int64 whose signed order and equality are exactly those of the f64
    values in `d` (callers canonicalise NaN / -0.0 first as their semantics
    require; +NaN sorts above +inf, the Spark order).

    With bit views: the usual sign-flipped IEEE bits. Without (TPU): the
    device's own f32 pair — hi = f32(d), lo = d - hi, both exact there —
    packed (order(hi) << 32) | unsigned order(lo). hi is monotone in d and
    lo breaks ties inside one hi, so the pair orders like d, and two
    doubles the device holds apart never share a code."""
    if f64_bit_views():
        bits = jax.lax.bitcast_convert_type(d, jnp.int64)
        return jnp.where(bits < 0, ~bits ^ jnp.int64(np.int64(-2**63)), bits)
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    # inf/NaN have no remainder (d - hi is NaN there); a zero remainder
    # must not carry a sign
    lo = jnp.where(jnp.isfinite(hi) & (lo != 0), lo, jnp.float32(0))
    return ((f32_order_bits(hi).astype(jnp.int64) << 32)
            + (f32_order_bits(lo).astype(jnp.int64) + jnp.int64(2**31)))


def pin_worker_to_cpu() -> None:
    """First call of every spawned worker process: the chip belongs to the
    one process that started the workers, and a second process that reaches
    for it fails or hangs. The environment variable alone is too late here —
    `spawn` imports the worker's module, hence the package, hence jax, before
    the worker's entry function runs, and jax reads JAX_PLATFORMS at import —
    so the config is set as well (the backend is not initialised yet); the
    variable still goes to any process the worker itself starts."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    `JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and nothing
    is done here. Otherwise the cache lives at `<checkout>/.jax_cache`
    (git-ignored): a fixed path, because the path is part of what a cache
    entry is found by. Call before the first compile, as chip_smoke.py does
    (chipbench places its own the same way)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
