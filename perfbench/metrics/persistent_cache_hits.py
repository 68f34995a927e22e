"""Entry + platform resolution: programs JAX loaded from the persistent compile cache in this process."""

from perfbench.readers import persistent_cache_hits as read  # noqa: F401
