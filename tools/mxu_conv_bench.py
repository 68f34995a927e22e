"""Can the MXU run the polynomial conv fast with exact small limbs?

Candidates at the operating batch (221k field elements):
  - current int32 32x12-bit band matmul (baseline, inside mont_mul)
  - bf16 48x8-bit einsum conv ('bi,bj,ijk->bk', f32 accumulation — exact
    for 8-bit limbs: products <= 65025, <=48 terms < 2^24)
  - int8 55x7-bit einsum conv (int32 accumulation — always exact)
  - two-stage: materialized outer product + band dot, bf16
Prints ms/conv; decides whether a 48x8 (or 55x7) fp rewrite can hit the
north star.
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from lodestar_tpu.ops import fp
from lodestar_tpu.utils import enable_compile_cache

enable_compile_cache()

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096 * 54
K = int(sys.argv[2]) if len(sys.argv) > 2 else 64

rng = np.random.default_rng(0)


def band(nl):
    t = np.zeros((nl * nl, 2 * nl), dtype=np.int32)
    for i in range(nl):
        for j in range(nl):
            t[i * nl + j, i + j] = 1
    return t


def band3(nl):
    t = np.zeros((nl, nl, 2 * nl), dtype=np.int32)
    for i in range(nl):
        for j in range(nl):
            t[i, j, i + j] = 1
    return t


def bench(name, fn, a, b, iters=3):
    # feed each conv's output back into the next iteration's operand —
    # K identical pure calls would be common-subexpression-eliminated to
    # ONE conv + K adds, timing the adds instead of the conv
    @jax.jit
    def f(x, y):
        nl = x.shape[1]
        for _ in range(K):
            r = fn(x, y)
            if x.dtype == jnp.int8:
                x = (x + r[:, :nl].astype(jnp.int8)) & 63
            elif x.dtype == jnp.int32:
                x = (x + r[:, :nl].astype(jnp.int32)) & 0xFFF
            else:
                x = jnp.mod(x + r[:, :nl].astype(x.dtype), jnp.asarray(256, x.dtype))
        return x[0, :1].astype(jnp.float32)

    np.asarray(f(a, b))
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(f(a, b))
    dt = (time.perf_counter() - t0) / iters / K
    print(f"{name:44s} {dt*1e3:8.3f} ms/conv", flush=True)


# baseline: current 32x12 int32 band matmul
a32 = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
b32 = jnp.asarray(rng.integers(0, 4096, size=(B, 32), dtype=np.int32))
T32 = jnp.asarray(band(32))


def conv_int32(x, y):
    outer = x[:, :, None] * y[:, None, :]
    return outer.reshape(B, 32 * 32) @ T32


bench("int32 32x12 outer+band (current)", conv_int32, a32, b32)

# bf16 48x8 einsum
a48 = jnp.asarray(rng.integers(0, 256, size=(B, 48), dtype=np.int32)).astype(jnp.bfloat16)
b48 = jnp.asarray(rng.integers(0, 256, size=(B, 48), dtype=np.int32)).astype(jnp.bfloat16)
T48 = jnp.asarray(band3(48)).astype(jnp.bfloat16)


def conv_bf16_einsum(x, y):
    return jnp.einsum("bi,bj,ijk->bk", x, y, T48, preferred_element_type=jnp.float32)


bench("bf16 48x8 einsum bi,bj,ijk->bk", conv_bf16_einsum, a48, b48)


# NOTE: an outer+band variant in bf16 would materialize 16-bit products
# in bf16 (8 significand bits) and is NOT exact — only the einsum form
# (f32 accumulation) preserves exactness, so only it is benchmarked.

# int8 55x7 einsum
a55 = jnp.asarray(rng.integers(0, 128, size=(B, 55), dtype=np.int8))
b55 = jnp.asarray(rng.integers(0, 128, size=(B, 55), dtype=np.int8))
T55 = jnp.asarray(band3(55)).astype(jnp.int8)


def conv_int8_einsum(x, y):
    return jnp.einsum("bi,bj,ijk->bk", x, y, T55, preferred_element_type=jnp.int32)


bench("int8 55x7 einsum bi,bj,ijk->bk", conv_int8_einsum, a55, b55)

# constant-operand conv as a plain matmul in bf16 (the m*P / t*P' halves)
M48 = jnp.asarray(rng.integers(0, 256, size=(48, 96), dtype=np.int32)).astype(jnp.bfloat16)


def const_conv_bf16(x, y):
    return jnp.dot(x, M48, preferred_element_type=jnp.float32)


bench("bf16 48x8 constant band matmul", const_conv_bf16, a48, b48)
print("done", flush=True)
