"""pow2-dispatch: arrays reaching a counted seam pass through the
shared pow-2 size-class padders.

The compiled-program cache is keyed by shape: a data-dependent leading
axis reaching a jitted program through a counted seam is one XLA
compile PER BATCH SIZE — a minutes-long compile storm at serving time,
exactly the failure the shared size-class padders (``ops/prep.pad_pow2``
/ ``pad_rows``, ``ssz/device_htr.pad_pow2_pairs``,
``models/batch_verify._pad_pow2``) exist to prevent.

The check is a backward slice at each ARRAY seam call site
(``_dispatch`` data args, ``_device_level``,
``device_batch_verify*`` — ``mesh_launch`` is exempt by contract: it
takes unpadded sets and pads inside the per-lane callables):

* PADDED — the slice (through local assignment chains) reaches a
  shared padder or another seam's output: quiet.
* RAW — the slice bottoms out at a host array constructor
  (``np.frombuffer`` / ``np.stack`` / ``np.asarray`` / ...) with no
  padder anywhere on the path AND the enclosing function never calls a
  padder at all: finding.
* UNKNOWN — parameters, attributes, helper-call results: quiet (the
  padding then happened upstream; the seam through which it arrived is
  checked at ITS call site).

The enclosing-function padder guard keeps sibling-variable flows
(pad applied to one array, concatenated via a helper into another)
from false-positives; the cost is that a function padding ONE of two
dispatched arrays stays quiet — the rule is a storm detector, not a
shape prover.
"""

from __future__ import annotations

import ast

from ..core import Finding, Rule, SourceFile
from ._device import last_segment

#: seam function name -> leading args to skip (the program callable for
#: _dispatch, the mesh for the sharded seam); mesh_launch is exempt by
#: contract (unpadded sets in, padding inside the lane callables)
SEAM_ARGS = {
    "_dispatch": 1,
    "_device_level": 0,
    "device_batch_verify": 0,
    "device_batch_verify_sharded": 1,
}

#: the shared size-class padders (plus the pad_* naming convention)
PADDERS = {"pad_pow2", "pad_rows", "pad_pow2_pairs", "_pad_pow2"}

#: host array constructors whose output shape follows their input
RAW_CONSTRUCTORS = {
    "array",
    "asarray",
    "ascontiguousarray",
    "frombuffer",
    "fromiter",
    "stack",
    "concatenate",
    "unpackbits",
    "packbits",
}

_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def _np_like_aliases(tree: ast.Module) -> set[str]:
    """numpy AND jax.numpy aliases — a jnp-constructed raw shape
    recompiles just the same."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name in ("numpy", "jax.numpy"):
                    out.add(a.asname or a.name.split(".")[-1])
        elif isinstance(node, ast.ImportFrom) and node.module == "jax":
            for a in node.names:
                if a.name == "numpy":
                    out.add(a.asname or "numpy")
    return out


def _is_padder_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    seg = last_segment(node.func)
    return seg is not None and (seg in PADDERS or seg.startswith("pad_"))


def _is_seam_call(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and last_segment(node.func) in SEAM_ARGS
    )


class _FunctionSlicer:
    """Backward slice through one function's local assignments."""

    def __init__(self, scope: ast.AST, np_aliases: set[str]):
        self.np_aliases = np_aliases
        self.assigns: dict[str, list[ast.AST]] = {}
        for node in ast.walk(scope):
            if node is not scope and isinstance(node, _SCOPES):
                continue
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for name in self._target_names(t):
                        self.assigns.setdefault(name, []).append(node.value)
            elif isinstance(node, ast.AugAssign) and isinstance(
                node.target, ast.Name
            ):
                self.assigns.setdefault(node.target.id, []).append(node.value)

    @staticmethod
    def _target_names(t: ast.AST) -> list[str]:
        if isinstance(t, ast.Name):
            return [t.id]
        if isinstance(t, (ast.Tuple, ast.List)):
            return [e.id for e in t.elts if isinstance(e, ast.Name)]
        return []

    def _is_raw_call(self, node: ast.AST) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        # np.frombuffer(...), np.stack(...); chained .reshape() etc. is
        # handled by walking the whole expression
        return (
            isinstance(f, ast.Attribute)
            and f.attr in RAW_CONSTRUCTORS
            and isinstance(f.value, ast.Name)
            and f.value.id in self.np_aliases
        )

    def verdict(self, expr: ast.AST, _seen: set[str] | None = None) -> str:
        """'padded' | 'raw' | 'unknown' for the expression's data."""
        seen = _seen if _seen is not None else set()
        padded = raw = False

        def walk(node: ast.AST) -> None:
            nonlocal padded, raw
            if _is_padder_call(node) or _is_seam_call(node):
                padded = True
                return  # a padder/seam output is padded regardless of input
            if self._is_raw_call(node):
                raw = True
            if isinstance(node, ast.Name) and node.id in self.assigns:
                if node.id not in seen:
                    seen.add(node.id)
                    for value in self.assigns[node.id]:
                        sub = self.verdict(value, seen)
                        if sub == "padded":
                            padded = True
                        elif sub == "raw":
                            raw = True
                return
            for child in ast.iter_child_nodes(node):
                walk(child)

        walk(expr)
        if padded:
            return "padded"
        if raw:
            return "raw"
        return "unknown"


class Pow2DispatchRule(Rule):
    name = "pow2-dispatch"
    description = (
        "arrays reaching a counted dispatch seam are padded to the "
        "shared pow-2 size classes — a data-dependent shape at a jitted "
        "program is one XLA compile per batch size (a compile storm)"
    )

    def check(self, sf: SourceFile):
        tree = sf.tree
        np_aliases = _np_like_aliases(tree)
        findings: list[Finding] = []

        # enclosing function scope per seam call
        scopes: list[tuple[ast.AST | None, ast.Call]] = []

        def collect(node: ast.AST, scope: ast.AST | None) -> None:
            for child in ast.iter_child_nodes(node):
                child_scope = child if isinstance(child, _SCOPES) else scope
                if isinstance(child, ast.Call) and _is_seam_call(child):
                    scopes.append((child_scope if child_scope is not None else None, child))
                collect(child, child_scope)

        collect(tree, None)

        slicers: dict[int, _FunctionSlicer] = {}
        for scope, call in scopes:
            if scope is None:
                continue  # module-level seam calls are counted-dispatch's turf
            slicer = slicers.get(id(scope))
            if slicer is None:
                slicer = slicers[id(scope)] = _FunctionSlicer(scope, np_aliases)
            fn_has_padder = any(
                _is_padder_call(n)
                for n in ast.walk(scope)
                if not (n is not scope and isinstance(n, _SCOPES))
            )
            if fn_has_padder:
                continue
            seam = last_segment(call.func)
            skip = SEAM_ARGS[seam]
            for arg in call.args[skip:]:
                if isinstance(arg, ast.Starred):
                    continue
                if slicer.verdict(arg) == "raw":
                    findings.append(
                        Finding(
                            self.name, sf.path, call.lineno,
                            f"unpadded data-dependent shape reaching counted "
                            f"seam '{seam}': the argument slices back to a "
                            "host array constructor with no shared pow-2 "
                            "padder (pad_pow2/pad_rows/pad_pow2_pairs) on "
                            "the path — one XLA compile per batch size at "
                            "serving time; pad to a size class first",
                        )
                    )
                    break  # one finding per call site
        return findings
