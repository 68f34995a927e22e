"""Which dtype/layout does this TPU actually execute fast?

Same chained-op harness as kernel_microbench (sync on scalar pull), but
over raw elementwise candidates: int32 vs float32 vs bfloat16 mul/add,
shift-based carries vs float floor carries, minor-dim 32 vs 128, and a
bf16 MXU matmul for scale.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from lodestar_tpu.utils import enable_compile_cache

enable_compile_cache()

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096 * 54
K = int(sys.argv[2]) if len(sys.argv) > 2 else 16

rng = np.random.default_rng(0)
ai32 = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
bi32 = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
af32 = ai32.astype(jnp.float32)
bf32 = bi32.astype(jnp.float32)
abf16 = ai32.astype(jnp.bfloat16)
bbf16 = bi32.astype(jnp.bfloat16)
ai32w = jnp.asarray(rng.integers(0, 4096, size=(B, 128), dtype=np.int32))
af32w = ai32w.astype(jnp.float32)


def timeit(name, f, *args, bytes_per_call=None, iters=3):
    g = jax.jit(f)
    np.asarray(g(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = np.asarray(g(*args))
    dt = (time.perf_counter() - t0) / iters / K
    gbps = (bytes_per_call or 0) / dt / 1e9
    print(f"{name:40s} {dt*1e3:9.3f} ms/call {gbps:8.1f} GB/s", flush=True)


ARR32 = B * 32 * 4
ARR128 = B * 128 * 4


def chain(op, x, y):
    for _ in range(K):
        x = op(x, y)
    return x[0, :1]


timeit("int32 mul+add (B,32)", lambda x, y: chain(lambda a, b: a * b + a, x, y), ai32, bi32, bytes_per_call=3 * ARR32)
timeit("float32 mul+add (B,32)", lambda x, y: chain(lambda a, b: a * b + a, x, y), af32, bf32, bytes_per_call=3 * ARR32)
timeit("bf16 mul+add (B,32)", lambda x, y: chain(lambda a, b: a * b + a, x, y), abf16, bbf16, bytes_per_call=3 * ARR32 // 2)
timeit("int32 add only (B,32)", lambda x, y: chain(lambda a, b: a + b, x, y), ai32, bi32, bytes_per_call=3 * ARR32)
timeit("int32 shift+mask (B,32)", lambda x, y: chain(lambda a, b: (a >> 12) + (b & 0xFFF), x, y), ai32, bi32, bytes_per_call=3 * ARR32)
timeit("f32 floor-carry (B,32)", lambda x, y: chain(lambda a, b: a - jnp.floor(a * (1 / 4096)) * 4096 + b, x, y), af32, bf32, bytes_per_call=3 * ARR32)
timeit("int32 mul+add (B,128)", lambda x, y: chain(lambda a, b: a * b + a, x, y), ai32w, ai32w, bytes_per_call=3 * ARR128)
timeit("f32 mul+add (B,128)", lambda x, y: chain(lambda a, b: a * b + a, x, y), af32w, af32w, bytes_per_call=3 * ARR128)

# conv via shifted FMAs in f32 at (B,64) out
def conv_f32(a, b):
    total = None
    for j in range(32):
        term = jnp.pad(a * b[:, j : j + 1], [(0, 0), (j, 32 - j)])
        total = term if total is None else total + term
    return total


timeit("conv shifted-FMA f32", lambda x, y: chain(lambda a, b: conv_f32(a, b)[:, :32], x, y), af32, bf32, bytes_per_call=4 * ARR32)

# bf16 matmul for scale: (B, 48) @ (48, 96)
w = jnp.asarray(rng.integers(0, 256, size=(48, 96), dtype=np.int32)).astype(jnp.bfloat16)
x48 = jnp.asarray(rng.integers(0, 256, size=(B, 48), dtype=np.int32)).astype(jnp.bfloat16)


def mm(a, _):
    return jnp.dot(a, w, preferred_element_type=jnp.float32).astype(jnp.bfloat16)


def mm_chain(x, y):
    for _ in range(K):
        x = mm(x, y)[:, :48]
    return x[0, :1].astype(jnp.float32)


timeit("bf16 MXU matmul (B,48)@(48,96)", mm_chain, x48, x48, bytes_per_call=int(1.5 * B * 48 * 2))

print("done", flush=True)
