"""Microbenchmark fp-kernel primitives on the real device (r5 core).

Measures, at the batch-verify operating shape (~221k field elements),
chained invocations of each primitive (k per launch, so per-call cost is
dispatch-amortized), syncing on a scalar device->host transfer. Chains
feed outputs back into inputs (CSE-proof; the r4 lesson).

Run: python tools/kernel_microbench.py [batch] [chain]
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp

from lodestar_tpu.ops import fp
from lodestar_tpu.ops import tower as tw
from lodestar_tpu.utils import enable_compile_cache

enable_compile_cache()

B = int(sys.argv[1]) if len(sys.argv) > 1 else 4096 * 54
K = int(sys.argv[2]) if len(sys.argv) > 2 else 16

rng = np.random.default_rng(0)


def rand_fp(n):
    vals = [int.from_bytes(rng.bytes(47), "big") % fp.P for _ in range(n)]
    return jnp.asarray(fp.limbs_from_ints(vals))


a = rand_fp(B)
b = rand_fp(B)

ARR = B * fp.LIMBS * 4  # one (B, 33) int32 pass


def chained(op):
    @jax.jit
    def f(x, y):
        for _ in range(K):
            x = op(x, y)
        return x[0, :1]  # tiny output: the sync point

    return f


def timeit(name, op, iters=3, passes_per_call=3, x=None, y=None):
    f = chained(op)
    x = a if x is None else x
    y = b if y is None else y
    np.asarray(f(x, y))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = np.asarray(f(x, y))
    dt = (time.perf_counter() - t0) / iters / K
    gbps = passes_per_call * ARR / dt / 1e9
    print(f"{name:34s} {dt*1e3:9.3f} ms/call  {gbps:7.1f} GB/s(min)", flush=True)
    return dt


timeit("mont_mul (relaxed)", fp.mont_mul)
timeit("mont_sq (relaxed)", lambda x, y: fp.mont_sq(x))
timeit("add", fp.add)
timeit("sub", fp.sub)
timeit("mul_acc + redc", lambda x, y: fp.redc(fp.mul_acc(x, y)))
timeit(
    "2 acc sum + 1 redc",
    lambda x, y: fp.redc(fp.acc_add(fp.mul_acc(x, y), fp.sq_acc(x))),
)

# tower shapes: fp2 at B/2, fp12 at B/12 keeps total element count ~B
a2 = a[: (B // 2) * 2].reshape(B // 2, 2, fp.LIMBS)
b2 = b[: (B // 2) * 2].reshape(B // 2, 2, fp.LIMBS)
timeit("fp2_mul (acc domain)", tw.fp2_mul, x=a2, y=b2)
n12 = B // 12
a12 = a[: n12 * 12].reshape(n12, 2, 3, 2, fp.LIMBS)
b12 = b[: n12 * 12].reshape(n12, 2, 3, 2, fp.LIMBS)
timeit("fp12_mul (12 redc)", tw.fp12_mul, x=a12, y=b12)
timeit("fp12_sq (karatsuba)", lambda x, y: tw.fp12_sq(x), x=a12, y=b12)

print("done", flush=True)
