"""The memlint rule set: one rule per serve-stack invariant.

Each rule names the PR that established its invariant (see
docs/INVARIANTS.md for the long-form rationale) and is deliberately
narrow — it matches the concrete syntactic shapes this repo uses, not
every conceivable violation, so a finding is near-certainly real and a
clean pass is cheap to keep. Every rule has a triggering fixture and a
clean-pass fixture in tests/test_analysis.py.
"""
from __future__ import annotations

import ast
import os
from typing import Dict, List, Set

from repro.analysis.core import ModuleCtx, rule


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------
def qualname(node: ast.AST) -> str:
    """Dotted name of a Name/Attribute chain ('' when not a plain chain)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def calls_in(node: ast.AST):
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            yield n


def _has_kw(call: ast.Call, name: str, value) -> bool:
    for kw in call.keywords:
        if kw.arg == name and isinstance(kw.value, ast.Constant) \
                and kw.value.value == value:
            return True
    return False


def _in_scope(ctx: ModuleCtx, *suffixes: str) -> bool:
    return any(s in ctx.rel if s.endswith("/") else ctx.rel.endswith(s)
               for s in suffixes)


# ---------------------------------------------------------------------------
# 1. deterministic top-k tie-break (PR 7: mesh/single-device exact parity)
# ---------------------------------------------------------------------------
@rule("topk-tiebreak",
      "top-k over similarity scores must use the deterministic "
      "(score desc, row id asc) tie-break — no lax.top_k, no unstable "
      "argsort — or mesh-sharded serve silently loses exact parity (PR 7)")
def topk_tiebreak(ctx: ModuleCtx) -> None:
    if not _in_scope(ctx, "repro/kernels/", "repro/core/retrieval.py",
                     "repro/core/residency.py"):
        return
    for call in calls_in(ctx.tree):
        q = qualname(call.func)
        if q.endswith("top_k") and ("lax" in q or q == "top_k"):
            ctx.report(call, "lax.top_k has implementation-defined tie "
                             "order; use a two-key lax.sort / merge_topk "
                             "(score desc, index asc)")
        elif q.endswith("argsort"):
            if not (_has_kw(call, "kind", "stable")
                    or _has_kw(call, "stable", True)):
                ctx.report(call, "unstable argsort on similarity scores "
                                 "breaks the (score desc, row id asc) "
                                 "tie-break contract; pass kind='stable' "
                                 "(numpy) or stable=True (jnp)")


# ---------------------------------------------------------------------------
# 2. commit-protocol renames are followed by a directory fsync (PR 3.1)
# ---------------------------------------------------------------------------
@rule("rename-fsync",
      "every os.rename/os.replace on a durability path must be followed by "
      "fsync_dir in the same function, or the committed directory entry can "
      "vanish on power loss and recovery drops acked writes (PR 3.1)")
def rename_fsync(ctx: ModuleCtx) -> None:
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        renames = []
        has_fsync_dir = False
        for call in calls_in(fn):
            q = qualname(call.func)
            if q in ("os.rename", "os.replace"):
                renames.append(call)
            elif q.endswith("fsync_dir"):
                has_fsync_dir = True
        # fsync_dir itself is the primitive; it contains no rename
        if renames and not has_fsync_dir and fn.name != "fsync_dir":
            for call in renames:
                ctx.report(call, f"os.{call.func.attr} in {fn.name}() has no "
                                 "fsync_dir in the same function — the "
                                 "renamed entry is not durable")


# ---------------------------------------------------------------------------
# 3. persistent-state mutations ride the journal (PR 3)
# ---------------------------------------------------------------------------
_MUTATORS = {"delete_session", "migrate_merge", "compact_tree"}
# journal.py IS the journaled path (ops + replay); maintenance.py defines the
# mutators (and may compose them internally).
_JOURNAL_MODULES = ("repro/core/journal.py", "repro/core/maintenance.py")


@rule("journaled-mutation",
      "persistent-state mutators (delete_session / migrate_merge / "
      "compact_tree) outside core/journal.py replay must route through a "
      "journaled DurableMemForest op, or a crash after the mutation "
      "recovers to a different state digest (PR 3)")
def journaled_mutation(ctx: ModuleCtx) -> None:
    if not ctx.rel.startswith("src/repro/") and "repro/" not in ctx.rel:
        return
    if _in_scope(ctx, *_JOURNAL_MODULES):
        return
    # bare names count only when imported from the maintenance module
    bare: Set[str] = set()
    for n in ast.walk(ctx.tree):
        if isinstance(n, ast.ImportFrom) and n.module \
                and n.module.endswith("maintenance"):
            bare.update(a.asname or a.name for a in n.names
                        if a.name in _MUTATORS)
    for call in calls_in(ctx.tree):
        q = qualname(call.func)
        name = q.rsplit(".", 1)[-1]
        if name not in _MUTATORS:
            continue
        if q.startswith("maintenance.") or q in bare:
            ctx.report(call, f"direct {name}() mutates persistent state "
                             "without a journal record; route through the "
                             "journaled DurableMemForest op")


# ---------------------------------------------------------------------------
# 4. replay / digest / snapshot determinism (PR 3)
# ---------------------------------------------------------------------------
_SET_ATTRS = {"applied_ops", "dirty_trees", "dirty"}
_DETERMINISM_SCOPE = ("repro/core/journal.py", "repro/core/persistence.py")


def _iter_nodes(tree: ast.AST):
    """(iterable expression, anchor node) pairs of every for-loop and
    comprehension generator."""
    for n in ast.walk(tree):
        if isinstance(n, (ast.For, ast.AsyncFor)):
            yield n.iter, n
        elif isinstance(n, (ast.ListComp, ast.SetComp, ast.DictComp,
                            ast.GeneratorExp)):
            for gen in n.generators:
                yield gen.iter, n


@rule("replay-determinism",
      "journal replay, digest computation, and snapshot serialization must "
      "be deterministic: no wall clocks, no random, no unordered-set "
      "iteration — or recovered state digests diverge run-to-run (PR 3)")
def replay_determinism(ctx: ModuleCtx) -> None:
    if not _in_scope(ctx, *_DETERMINISM_SCOPE):
        return
    for call in calls_in(ctx.tree):
        q = qualname(call.func)
        if q in ("time.time", "time.time_ns", "time.perf_counter",
                 "time.monotonic"):
            ctx.report(call, f"{q}() in a replay/serialization module makes "
                             "recovered state timing-dependent")
        elif q.startswith(("random.", "np.random.", "numpy.random.",
                           "jax.random.")):
            ctx.report(call, f"{q}() in a replay/serialization module makes "
                             "recovered state nondeterministic")
    for it, anchor in _iter_nodes(ctx.tree):
        if isinstance(it, ast.Set) \
                or (isinstance(it, ast.Call) and qualname(it.func) == "set"):
            ctx.report(anchor, "iterating a set directly: order is "
                               "arbitrary — wrap in sorted()")
        elif isinstance(it, ast.Attribute) and it.attr in _SET_ATTRS:
            ctx.report(anchor, f"iterating .{it.attr} (a set) directly: "
                               "order is arbitrary — wrap in sorted()")


# ---------------------------------------------------------------------------
# 5. spans only via context manager (PR 9)
# ---------------------------------------------------------------------------
@rule("span-context",
      "spans are opened only as `with obs.span(...)` — a manual __enter__ "
      "leaks the span onto the thread-local stack on any exception and "
      "corrupts every later span's parentage (PR 9)")
def span_context(ctx: ModuleCtx) -> None:
    if _in_scope(ctx, "repro/obs/"):
        return                      # the implementation layer itself
    with_items: Set[int] = set()
    for n in ast.walk(ctx.tree):
        if isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                with_items.add(id(item.context_expr))
    for call in calls_in(ctx.tree):
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "span":
            if id(call) not in with_items:
                ctx.report(call, "span() result used outside a with "
                                 "statement — open spans only via the "
                                 "context manager")
        if isinstance(func, ast.Attribute) and func.attr == "__enter__":
            ctx.report(call, "manual __enter__ call — use a with statement")


# ---------------------------------------------------------------------------
# 6. every Pallas kernel has a referenced ref.py oracle (PRs 2/7)
# ---------------------------------------------------------------------------
# kernel module stem -> (ref.py oracle name, ops-layer entry point)
_KERNEL_ALIASES: Dict[str, str] = {"flash_attention": "attention"}


@rule("kernel-parity",
      "every Pallas kernel module in kernels/ needs a ref.py oracle that a "
      "parity test references — an unoracled kernel's numerics drift "
      "silently (PRs 2/7)")
def kernel_parity(ctx: ModuleCtx) -> None:
    parts = ctx.rel.split("/")
    if len(parts) < 2 or parts[-2] != "kernels":
        return
    stem = parts[-1][:-3]
    if stem in ("ref", "ops", "__init__"):
        return
    if not any(qualname(c.func).endswith("pallas_call")
               for c in calls_in(ctx.tree)):
        return
    base = _KERNEL_ALIASES.get(stem, stem)
    ref_name = f"{base}_ref"
    kernels_dir = os.path.dirname(ctx.path)
    if ref_name not in ctx.project.ref_functions(kernels_dir):
        ctx.report(1, f"Pallas kernel module has no {ref_name}() oracle in "
                      "kernels/ref.py")
        return
    tests = ctx.project.tests_text()
    if ref_name not in tests and f"ops.{base}(" not in tests:
        ctx.report(1, f"kernel oracle {ref_name}() is not referenced by any "
                      "test under tests/ — parity is unchecked")


# ---------------------------------------------------------------------------
# 7. no host sync inside ServeEngine.step phase bodies (PRs 1/2/9)
# ---------------------------------------------------------------------------
_PHASE_METHODS = {"step", "_admit", "_drain_ingest", "_drain_queries",
                  "_drain_maintenance", "_drain_residency"}
_SYNC_CALLS = {"np.asarray", "numpy.asarray", "jax.device_get"}


def _mentions_jax(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id in ("jnp", "jax"):
            return True
    return False


@rule("host-sync",
      "no host-synchronizing calls (np.asarray / block_until_ready / "
      "device_get / float() on device arrays) inside ServeEngine.step "
      "phase bodies — a hidden sync serializes the decode cadence "
      "(PRs 1/2/9)")
def host_sync(ctx: ModuleCtx) -> None:
    if "serving/" not in ctx.rel:
        return
    engine_cls = next(
        (n for n in ast.walk(ctx.tree)
         if isinstance(n, ast.ClassDef) and n.name == "ServeEngine"), None)
    if engine_cls is None:
        return
    for fn in engine_cls.body:
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or fn.name not in _PHASE_METHODS:
            continue
        for call in calls_in(fn):
            q = qualname(call.func)
            if q in _SYNC_CALLS:
                ctx.report(call, f"{q}() forces a device->host sync inside "
                                 f"{fn.name}()")
            elif isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "block_until_ready":
                ctx.report(call, "block_until_ready() inside "
                                 f"{fn.name}() stalls the decode loop")
            elif q in ("float", "int") and call.args \
                    and _mentions_jax(call.args[0]):
                ctx.report(call, f"{q}() on a jax expression inside "
                                 f"{fn.name}() forces a device->host sync")
