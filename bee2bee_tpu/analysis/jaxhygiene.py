"""Pass family 3: JAX hygiene (ML-J*).

The engine's throughput rests on jit hot paths staying on-device: one
implicit host sync per decode step erases the paged-cache and batching
wins with a device→host round trip the profiler shows only as "gap".
Rules, applied to jit-compiled functions in engine/, models/, ops/,
parallel/:

- ML-J001 — implicit host sync inside a jit-reachable function:
  ``.item()`` / ``.tolist()`` / ``.block_until_ready()``, ``np.asarray``/
  ``np.array``/``np.frombuffer`` on the numpy (not jax.numpy) alias, or a
  ``float()``/``int()``/``bool()`` cast of a function parameter (traced
  values fail or sync there; static config belongs in static_argnums).
- ML-J002 — Python branching on a traced value: an ``if``/``while`` test
  built from ``jnp.*``/``jax.lax``/``lax.*`` calls raises
  TracerBoolConversionError at trace time or, worse, burns the first
  trace's branch into the compiled graph. Use ``jnp.where`` /
  ``lax.cond``.
- ML-J003 — host sync inside the scheduler's decode hot-loop region:
  ``.item()``/``.tolist()``/``.block_until_ready()``, ``np.asarray``/
  ``np.array`` on the numpy alias, or ``jax.device_get`` lexically inside
  the step-loop methods (engine/scheduler.py ``_step`` and the window
  helpers it drives). The overlap design (docs/PERF.md "Decode hot
  loop") permits exactly ONE host sync per readback window — the token
  fetch in ``_fetch_window`` / the verdict fetch in ``_spec_step``,
  each carrying a same-line suppression naming itself. Any other sync
  in the region serializes the device behind host work the async ring
  exists to overlap, and every occurrence must argue its case in a
  suppression reason.

"jit-reachable" is resolved statically: functions decorated with
``@jax.jit`` (directly or via partial), functions/methods wrapped as
``x = jax.jit(fn)``, lambdas inside ``jax.jit(...)``, bodies passed
to ``jax.lax.scan/cond/while_loop/fori_loop/switch``, shard_map bodies,
and pallas kernel bodies passed to ``pl.pallas_call(kernel, ...)``
(directly or via partial) — a host sync inside a pallas kernel fails to
lower on real TPU and silently de-optimizes interpret mode.
"""

from __future__ import annotations

import ast

from .core import dotted_name as _dotted

_SCOPES = ("engine/", "models/", "ops/", "parallel/")
_HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_NP_HOST_FNS = {"asarray", "array", "frombuffer", "copy"}
_LAX_WRAPPERS = {"scan", "cond", "while_loop", "fori_loop", "switch"}
_CAST_NAMES = {"float", "int", "bool"}
# the decode hot-loop region (ML-J003): the scheduler step loop and the
# window helpers it drives every readback. Matched by METHOD NAME within
# engine/ files — the region is a contract on these names, so a renamed
# helper must update this set (the known-bad fixture in test_meshlint
# pins the coverage)
_HOT_LOOP_FNS = {
    "_turn",
    "_step",
    "_spec_step",
    "_dispatch_window",
    "_overlap_ready",
    "_fetch_window",
    "_process_window",
    "_drain_inflight",
    "_process_row_tokens",
    "_settle_window",
    "_settle_row",
    "_deliver_pending",
    "_deliver_row",
}


class _Aliases:
    def __init__(self, tree: ast.AST):
        self.numpy: set[str] = set()
        self.jnp: set[str] = set()
        self.lax: set[str] = set()
        self.jit_names: set[str] = {"jax.jit"}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    bound = a.asname or a.name.split(".")[0]
                    if a.name == "numpy":
                        self.numpy.add(bound)
                    elif a.name == "jax.numpy":
                        self.jnp.add(a.asname or "jax.numpy")
                    elif a.name == "jax.lax":
                        self.lax.add(a.asname or "jax.lax")
            elif isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                for a in node.names:
                    if mod == "jax" and a.name == "jit":
                        self.jit_names.add(a.asname or "jit")
                    elif mod == "jax" and a.name == "lax":
                        self.lax.add(a.asname or "lax")
                    elif mod == "jax" and a.name == "numpy":
                        self.jnp.add(a.asname or "numpy")

    def is_jit(self, name: str) -> bool:
        return name in self.jit_names

    def is_traced_ns(self, name: str) -> bool:
        """dotted call base that yields traced arrays (jnp.*, lax.*)."""
        base = name.rsplit(".", 1)[0] if "." in name else ""
        return base in self.jnp or base in self.lax or base in ("jax.lax", "jax.numpy")


class JaxHygienePass:
    family = "jax"
    rules = {
        "ML-J001": "implicit host sync inside a jit-compiled function",
        "ML-J002": "Python branch on a traced value inside jit",
        "ML-J003": "host sync inside the scheduler's decode hot-loop region",
    }

    def applies(self, path: str) -> bool:
        return path.startswith(_SCOPES)

    def run(self, ctx) -> list:
        al = _Aliases(ctx.tree)
        roots = self._collect_jit_roots(ctx.tree, al)
        findings: list = []
        seen: set[int] = set()
        for fn in roots:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            params = self._params(fn)
            for node in ast.walk(fn):
                self._check(ctx, node, al, params, findings)
        if ctx.path.startswith("engine/"):
            for fn in ast.walk(ctx.tree):
                if (
                    isinstance(fn, ast.FunctionDef)
                    and fn.name in _HOT_LOOP_FNS
                    and id(fn) not in seen  # a jit root got ML-J001 already
                ):
                    for node in ast.walk(fn):
                        self._check_hot_loop(ctx, node, al, findings)
        return findings

    # -------------------------------------------------------------- roots

    def _params(self, fn) -> set[str]:
        a = fn.args  # FunctionDef and Lambda share the arguments layout
        names = {x.arg for x in list(a.args) + list(a.kwonlyargs) + list(a.posonlyargs)}
        names.discard("self")
        return names

    def _collect_jit_roots(self, tree: ast.AST, al: _Aliases) -> list:
        by_name: dict[str, list] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                by_name.setdefault(node.name, []).append(node)
        # `kernel = functools.partial(_kernel, ...)` then
        # `pl.pallas_call(kernel, ...)` — the ops/ kernel wiring binds the
        # partial to a local first, so follow Name→partial hops. Keyed by
        # bare name across the file, so two functions reusing the same
        # local name collide: keep EVERY binding and mark them all — an
        # over-approximation scans extra functions, never misses one.
        partial_bindings: dict[str, list] = {}
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)
                and _dotted(node.value.func) in ("partial", "functools.partial")
                and node.value.args
            ):
                partial_bindings.setdefault(node.targets[0].id, []).append(
                    node.value.args[0]
                )
        roots: list = []
        visited_bindings: set[int] = set()  # no revisit loop on cycles

        def mark(expr: ast.AST):
            if isinstance(expr, ast.Lambda):
                roots.append(expr)
            elif isinstance(expr, ast.Name):
                roots.extend(by_name.get(expr.id, ()))
                for bound in partial_bindings.get(expr.id, ()):
                    if id(bound) not in visited_bindings:
                        visited_bindings.add(id(bound))
                        mark(bound)
            elif isinstance(expr, ast.Attribute):  # self._decode_fn
                roots.extend(by_name.get(expr.attr, ()))
            elif isinstance(expr, ast.Call) and expr.args and _dotted(
                expr.func
            ) in ("partial", "functools.partial"):
                mark(expr.args[0])  # shard_map(partial(body, ...), ...)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    name = _dotted(dec)
                    if al.is_jit(name):
                        roots.append(node)
                    elif isinstance(dec, ast.Call):
                        cname = _dotted(dec.func)
                        if al.is_jit(cname):
                            roots.append(node)
                        elif cname in ("partial", "functools.partial") and dec.args:
                            if al.is_jit(_dotted(dec.args[0])):
                                roots.append(node)
            elif isinstance(node, ast.Call):
                name = _dotted(node.func)
                if al.is_jit(name) and node.args:
                    mark(node.args[0])
                elif name.rsplit(".", 1)[-1] == "shard_map" and node.args:
                    # SPMD bodies are traced exactly like jit bodies (the
                    # compat shim resolves to jax's shard_map either way)
                    mark(node.args[0])
                elif name.rsplit(".", 1)[-1] == "pallas_call" and node.args:
                    # pallas kernels (ops/flash.py, ops/ragged.py) are
                    # traced into Mosaic: host syncs / Python branches on
                    # traced values fail to lower on real TPU — the kernel
                    # body (often functools.partial(kernel, ...)) is a root
                    mark(node.args[0])
                elif (
                    name.rsplit(".", 1)[-1] in _LAX_WRAPPERS
                    and al.is_traced_ns(name)
                    and node.args
                ):
                    # scan(body, ...) / cond(pred, true_fn, false_fn, ...)
                    for arg in node.args[: 3 if name.endswith("cond") else 1]:
                        mark(arg)
        return roots

    # ------------------------------------------------------------- checks

    def _check(self, ctx, node, al: _Aliases, params: set, findings: list):
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            last = name.rsplit(".", 1)[-1]
            if isinstance(node.func, ast.Attribute) and last in _HOST_SYNC_ATTRS:
                findings.append(
                    ctx.finding(
                        "ML-J001",
                        node,
                        f".{last}() inside a jit-compiled function",
                        "forces a device→host sync (or fails under trace) — "
                        "keep the value on-device or move the sync outside jit",
                    )
                )
            elif (
                "." in name
                and name.rsplit(".", 1)[0] in al.numpy
                and last in _NP_HOST_FNS
            ):
                findings.append(
                    ctx.finding(
                        "ML-J001",
                        node,
                        f"{name}() materializes a host array inside jit",
                        "use jnp.* on-device; np.* forces a transfer per call",
                    )
                )
            elif (
                isinstance(node.func, ast.Name)
                and node.func.id in _CAST_NAMES
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id in params
            ):
                findings.append(
                    ctx.finding(
                        "ML-J001",
                        node,
                        f"{node.func.id}() cast of parameter "
                        f"{node.args[0].id!r} inside jit",
                        "a traced argument syncs (or raises) here — mark it "
                        "static_argnums or keep it an array",
                    )
                )
        elif isinstance(node, (ast.If, ast.While)):
            for sub in ast.walk(node.test):
                if isinstance(sub, ast.Call) and al.is_traced_ns(_dotted(sub.func)):
                    findings.append(
                        ctx.finding(
                            "ML-J002",
                            node,
                            "Python branch on a traced expression inside jit",
                            "trace-time TracerBoolConversionError (or a "
                            "burned-in branch) — use jnp.where / lax.cond",
                        )
                    )
                    break

    def _check_hot_loop(self, ctx, node, al: _Aliases, findings: list):
        """ML-J003: the decode hot loop's sync budget is ONE fetch per
        readback window. Every .item()/.tolist()/.block_until_ready(),
        numpy-alias materialization, or jax.device_get in the region is a
        finding — the sanctioned fetches carry same-line suppressions
        whose reasons name the contract."""
        if not isinstance(node, ast.Call):
            return
        name = _dotted(node.func)
        last = name.rsplit(".", 1)[-1]
        if isinstance(node.func, ast.Attribute) and last in _HOST_SYNC_ATTRS:
            findings.append(
                ctx.finding(
                    "ML-J003",
                    node,
                    f".{last}() inside the decode hot-loop region",
                    "blocks the step loop on a device→host sync the "
                    "readback ring did not schedule — batch it into the "
                    "window fetch or move it off the hot path",
                )
            )
        elif (
            "." in name
            and name.rsplit(".", 1)[0] in al.numpy
            and last in _NP_HOST_FNS
        ):
            findings.append(
                ctx.finding(
                    "ML-J003",
                    node,
                    f"{name}() in the decode hot-loop region",
                    "materializing a device value here serializes the "
                    "device behind host work — only the per-window token "
                    "fetch may sync (suppress with the contract's reason)",
                )
            )
        elif last == "device_get":
            findings.append(
                ctx.finding(
                    "ML-J003",
                    node,
                    "jax.device_get() in the decode hot-loop region",
                    "an unscheduled host sync in the step loop — the "
                    "overlap design permits one fetch per readback window "
                    "(suppress with the contract's reason)",
                )
            )
