"""The root cells' plain reference: hashlib over the same leaves."""

from __future__ import annotations

import hashlib


def levels_from_leaves(leaves: bytes) -> list[bytes]:
    """Every level of the binary SHA-256 tree over 32-byte leaves, leaf
    level first, the 32-byte root last."""
    sha = hashlib.sha256
    levels = [leaves]
    while len(levels[-1]) > 32:
        below = levels[-1]
        levels.append(b"".join(sha(below[i : i + 64]).digest() for i in range(0, len(below), 64)))
    return levels


def root(leaves: bytes) -> bytes:
    return levels_from_leaves(leaves)[-1]
