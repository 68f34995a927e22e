"""Cross-cutting helpers: retry, sleep, byte utils, math.

Counterpart of the reference `packages/utils/src` (sleep.ts, retry.ts,
bytes.ts, math.ts). Merkle-branch verification lives in
`lodestar_tpu.ssz.merkle.verify_merkle_branch` (reference
`utils/src/verifyMerkleBranch.ts`).
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Awaitable, Callable, TypeVar

T = TypeVar("T")

__all__ = [
    "sleep",
    "backoff_delay",
    "retry",
    "retry_sync",
    "bytes_to_int",
    "int_to_bytes",
    "to_hex",
    "from_hex",
    "xor_bytes",
    "int_div_ceil",
    "bit_length",
    "ErrorAborted",
    "TimeoutError_",
    "enable_compile_cache",
    "AcceleratorUnavailable",
    "probe_accelerator",
]


def enable_compile_cache() -> str:
    """Persistent XLA compile cache, placed from outside or at one fixed
    path. Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself
    and no directory is set here; otherwise the cache is
    `<checkout>/.jax_cache`, found from this file and never from the
    working directory (the path is part of every entry's key, so a
    directory that moves never hits). Every process that compiles a
    verify program calls this once at start. Returns the directory."""
    import os

    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        checkout = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        cache_dir = os.path.join(checkout, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    return cache_dir


class AcceleratorUnavailable(RuntimeError):
    """The JAX backend could not initialise — on a chip host, typically
    because the chip belongs to another process."""


def probe_accelerator() -> dict:
    """Initialise this process's JAX backend (taking the chip where
    there is one) and describe it as JAX reports it: `platform`,
    `device_kind`, `count`. The processes that build a verifier call
    this once at start and resolve every "auto" from the answer;
    processes that build none (validator client, light client, db
    tools) never do, so they never take the chip.

    An accelerator belongs to one process at a time, so a second node
    or server on a chip host fails here. That is raised with the ways
    out, never answered with a silent CPU fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise AcceleratorUnavailable(
            f"cannot initialise the JAX backend: {e}\n"
            "An accelerator belongs to one process at a time. If another node "
            "or offload server on this host owns the chip, either start this "
            "process with JAX_PLATFORMS=cpu (it then verifies and hashes on the "
            "CPU) or route its verification to the owner with --bls-offload "
            "HOST:PORT."
        ) from e
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
    }


class ErrorAborted(Exception):
    """Operation cancelled by an abort signal (reference utils/errors.ts)."""


TimeoutError_ = asyncio.TimeoutError


async def sleep(seconds: float) -> None:
    await asyncio.sleep(seconds)


def backoff_delay(
    attempt: int,
    *,
    base: float,
    factor: float = 2.0,
    max_delay: float | None = None,
    jitter: float = 0.0,
    rng: Callable[[], float] = random.random,
) -> float:
    """Delay before retry number `attempt` (0-based): exponential
    `base * factor**attempt`, capped at `max_delay`, with up to
    `jitter` fraction of the capped delay SUBTRACTED (jitter spreads a
    fleet of breakers opened by the same outage so they don't re-probe
    the recovering host in lockstep — downward, so the documented cap
    is a true upper bound even at saturation, where upward jitter
    would both exceed it and collapse back into lockstep). Used by
    utils.retry's backoff mode and the offload circuit breaker's
    half-open schedule."""
    if attempt < 0:
        raise ValueError("attempt must be >= 0")
    delay = base * (factor ** attempt)
    if max_delay is not None:
        delay = min(delay, max_delay)
    if jitter:
        delay -= delay * jitter * rng()
    return delay


def _retry_delay_for(
    attempt: int,
    retry_delay: float,
    backoff_factor: float | None,
    max_delay: float | None,
    jitter: float,
) -> float:
    """Fixed delay unless a backoff factor is given (keeps every
    existing fixed-delay caller's behavior bit-for-bit)."""
    if backoff_factor is None:
        return retry_delay
    return backoff_delay(
        attempt, base=retry_delay, factor=backoff_factor, max_delay=max_delay, jitter=jitter
    )


async def retry(
    fn: Callable[[], Awaitable[T]],
    *,
    retries: int = 3,
    retry_delay: float = 0.0,
    backoff_factor: float | None = None,
    max_delay: float | None = None,
    jitter: float = 0.0,
    should_retry: Callable[[Exception], bool] | None = None,
) -> T:
    """Async retry (reference `utils/src/retry.ts`). Default is the
    reference's fixed delay; passing `backoff_factor` switches to
    exponential backoff (`retry_delay * factor**attempt`) with an
    optional `max_delay` cap and `jitter` fraction.

    Only `Exception` is retried: cancellation (CancelledError) and
    KeyboardInterrupt propagate immediately.
    """
    if retries < 1:
        raise ValueError("retries must be >= 1")
    last: Exception | None = None
    for attempt in range(retries):
        try:
            return await fn()
        except Exception as e:
            if should_retry is not None and not should_retry(e):
                raise
            last = e
            if attempt < retries - 1 and retry_delay:
                await asyncio.sleep(
                    _retry_delay_for(attempt, retry_delay, backoff_factor, max_delay, jitter)
                )
    assert last is not None
    raise last


def retry_sync(
    fn: Callable[[], T],
    *,
    retries: int = 3,
    retry_delay: float = 0.0,
    backoff_factor: float | None = None,
    max_delay: float | None = None,
    jitter: float = 0.0,
    should_retry: Callable[[Exception], bool] | None = None,
) -> T:
    if retries < 1:
        raise ValueError("retries must be >= 1")
    last: Exception | None = None
    for attempt in range(retries):
        try:
            return fn()
        except Exception as e:
            if should_retry is not None and not should_retry(e):
                raise
            last = e
            if attempt < retries - 1 and retry_delay:
                time.sleep(
                    _retry_delay_for(attempt, retry_delay, backoff_factor, max_delay, jitter)
                )
    assert last is not None
    raise last


def bytes_to_int(data: bytes, endianness: str = "little") -> int:
    return int.from_bytes(data, endianness)  # type: ignore[arg-type]


def int_to_bytes(value: int, length: int, endianness: str = "little") -> bytes:
    return value.to_bytes(length, endianness)  # type: ignore[arg-type]


def to_hex(data: bytes) -> str:
    return "0x" + data.hex()


def from_hex(s: str) -> bytes:
    return bytes.fromhex(s[2:] if s.startswith("0x") else s)


def xor_bytes(a: bytes, b: bytes) -> bytes:
    return bytes(x ^ y for x, y in zip(a, b))


def int_div_ceil(a: int, b: int) -> int:
    return -(-a // b)


def bit_length(n: int) -> int:
    return n.bit_length()
