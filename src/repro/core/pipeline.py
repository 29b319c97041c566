"""Compiled extraction pipelines: one fused jitted executable per PlanUnit.

The eager executor (:mod:`repro.core.executor`) runs every join in two
phases — an exact ``join_count`` with a host round-trip to size the output,
then a fresh XLA compile per distinct capacity.  That materialization
barrier per operator is exactly what GraphGen and the Vertica graph work
identify as the cost of operator-at-a-time extraction.  This module removes
it:

* **Capacity planning** — the cost model's cardinality estimates
  (:func:`repro.core.cost.step_expansions`) pre-size every intermediate to a
  pow-2-bucketed static capacity *before* execution.
* **Whole-unit tracing** — each :class:`~repro.core.planner.PlanUnit`'s full
  dataflow (scans → join chain → post-filters → outer-join branches → edge
  projection) is traced into **one** jitted executable with no host syncs in
  the middle.  Joins report their exact required row count on-device; the
  driver syncs once per unit, and an overflowed step triggers a single
  re-execution at the (bucketed) exact capacity.
* **Executable caching** — compiled executables are content-addressed by
  (unit signature, join orders, capacity-bucket vector, input-schema
  fingerprint, kernel flags) in a process-wide store, so a cold query on a
  warm engine — or a warm executable cache replayed against cold data —
  skips re-tracing and re-compiling entirely.
* **Pallas kernels** — with ``use_kernel`` (auto-on on TPU via
  :func:`repro.kernels.ops.resolve_use_kernel`) each join prunes probe rows
  through a ``bloom`` semi-join prefilter before the capacity expansion;
  the ``sorted_probe`` join probe runs only when ``use_kernel=True`` forces
  it (the TPU default probes with XLA ``searchsorted``); off-TPU the jnp
  reference paths are used.

Bag semantics are identical to the eager path (the parity contract tested
in ``tests/test_pipeline.py``): capacities only change padding, never the
set of valid rows.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import os
import queue
import re
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.compilation_cache import compilation_cache

from repro import obs
from repro.core.cost import estimate_query, scan_estimate, step_expansions
from repro.core.database import Database
from repro.core.executor import edge_output, qualified_cond, scan_table
from repro.core.jsoj import MergedQuery, shared_query
from repro.core.model import JoinQuery, join_schedule, query_signature
from repro.kernels.ops import bloom_bits_for, resolve_use_kernel
from repro.relational import Table, dedup
from repro.relational.join import (
    _round_capacity,
    join_with_capacity,
    left_outer_with_capacity,
)

# Safety factor applied to cardinality estimates before pow-2 bucketing;
# System-R estimates undershoot under Zipf skew, and a bucket that survives
# the first run saves a whole retry (re-execution, possibly re-compile).
CAPACITY_MARGIN = 2.0

# Units whose largest intermediate fits under this capacity compile tiered:
# a fast low-optimization XLA build serves the cold request (full
# optimization costs ~3x the compile time for single-digit-ms wins on small
# buffers) while a background thread rebuilds at full optimization and swaps
# it into the cache for warm requests.
TIER_MAX_CAPACITY = 1 << 16

_EXECUTABLE_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_EXECUTABLE_CACHE_SIZE = 256
_CACHE_LOCK = threading.Lock()

# Single daemon worker draining re-optimization jobs: one thread so the
# rebuild trickle never starves the foreground of cores, daemonized so a
# short-lived process (a script, pytest) exits without waiting for
# discarded full-opt rebuilds.
_REOPT_QUEUE: "queue.Queue" = queue.Queue()
_REOPT_THREAD: Optional[threading.Thread] = None
_REOPT_START_LOCK = threading.Lock()


def clear_executable_cache() -> None:
    """Drop every AOT-compiled unit executable (process-wide store)."""
    with _CACHE_LOCK:
        _EXECUTABLE_CACHE.clear()


# On-disk XLA compilation cache ("cold-start elimination"): a restarted
# process re-lowers each unit but skips the XLA compile.  One rule, applied
# on engine start-up: where JAX_COMPILATION_CACHE_DIR is set JAX keeps its
# cache there and no other directory is set in code; otherwise the cache
# lives at a fixed directory of the checkout, because the path is part of
# what a later process must find again.  Process-global because the
# underlying JAX config is.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")
_PERSISTENT_CACHE_DIR: Optional[str] = None
_PERSISTENT_CACHE_LOCK = threading.Lock()

log = logging.getLogger("repro.pipeline")


def enable_persistent_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The directory is ``JAX_COMPILATION_CACHE_DIR`` when that is set, else
    :data:`DEFAULT_CACHE_DIR`.  Idempotent.  Thresholds are lowered so even
    small unit executables are persisted.
    """
    global _PERSISTENT_CACHE_DIR
    with _PERSISTENT_CACHE_LOCK:
        if _PERSISTENT_CACHE_DIR is None:
            path = (os.environ.get(CACHE_DIR_ENV)
                    or os.path.normpath(DEFAULT_CACHE_DIR))
            os.makedirs(path, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", path)
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                              -1)
            # a cache JAX already opened keeps its directory until reset
            compilation_cache.reset_cache()
            _PERSISTENT_CACHE_DIR = path
        return _PERSISTENT_CACHE_DIR


def persistent_compilation_cache_dir() -> Optional[str]:
    """The directory enabled via :func:`enable_persistent_compilation_cache`
    (``None`` until the first engine starts)."""
    return _PERSISTENT_CACHE_DIR


@contextlib.contextmanager
def persistent_compilation_cache_off():
    """Compile without reading or writing the persistent cache.

    For a measurement that must pay every compile (a cold start), and for
    AOT compiles for a described chip, whose entries no process could load.
    """
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


REOPT_FAILURES = "pipeline_reoptimization_failures_total"


def _submit_reopt(job) -> None:
    global _REOPT_THREAD
    with _REOPT_START_LOCK:
        if _REOPT_THREAD is None or not _REOPT_THREAD.is_alive():
            def worker():
                while True:
                    task = _REOPT_QUEUE.get()
                    try:
                        task()
                    except Exception:
                        # the opt-0 build keeps serving; count and log so a
                        # failed full-opt rebuild is never silent
                        log.exception("background full-optimization "
                                      "recompile failed")
                        obs.failure_counter(REOPT_FAILURES).inc()
                    finally:
                        _REOPT_QUEUE.task_done()

            _REOPT_THREAD = threading.Thread(
                target=worker, daemon=True, name="pipeline-reopt")
            _REOPT_THREAD.start()
    _REOPT_QUEUE.put(job)


def drain_reoptimizations(timeout: Optional[float] = None) -> None:
    """Block until queued background re-optimizations have finished.

    Warm-path measurements should call this first: tiered cold builds leave
    full-optimization rebuilds in flight, and on small machines the rebuild
    thread competes with whatever is being timed.  Rebuilds that failed are
    counted in ``pipeline_reoptimization_failures_total``.
    """
    if _REOPT_THREAD is None:
        return
    if timeout is None:
        _REOPT_QUEUE.join()
        return
    deadline = time.monotonic() + timeout
    with _REOPT_QUEUE.all_tasks_done:
        while _REOPT_QUEUE.unfinished_tasks:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or not _REOPT_QUEUE.all_tasks_done.wait(
                    remaining):
                return


def tiered_compile(lowered, small: bool, store):
    """Compile a lowered computation, optionally in two tiers.

    ``small`` computations build at XLA optimization level 0 — about 3x
    faster to compile for single-digit-ms run-time cost on small buffers —
    and a background thread rebuilds at full optimization, handing the
    result to ``store`` (which must be safe to call from another thread;
    it also receives the fast build synchronously).  Large computations
    compile fully up front: their run time dominates, so skimping on
    optimization would cost more than it saves.
    """
    if not small:
        exe = lowered.compile()
        store(exe)
        return exe
    exe = lowered.compile(
        compiler_options={"xla_backend_optimization_level": 0})
    store(exe)

    _submit_reopt(lambda: store(lowered.compile()))
    return exe


def cached_tiered_compile(cache, lock, key, lower, small: bool,
                          max_size: int, on_reoptimized=None):
    """Shared lookup-or-compile plumbing for AOT executable caches.

    Returns ``(executable, hit)``.  On a miss, ``lower()`` is called to
    produce the lowered computation, which compiles via
    :func:`tiered_compile`; the store closure is eviction-aware (a key
    evicted before its background upgrade lands is not resurrected) and
    LRU-trims ``cache`` to ``max_size`` under ``lock``.
    ``on_reoptimized`` fires when a background full-opt rebuild swaps in.
    """
    with lock:
        exe = cache.get(key)
        if exe is not None:
            cache.move_to_end(key)
            return exe, True
    first = []

    def store(built):
        with lock:
            if first:
                if key not in cache:
                    return          # evicted before the upgrade landed
                if on_reoptimized is not None:
                    on_reoptimized()
            first.append(True)
            cache[key] = built
            while len(cache) > max_size:
                cache.popitem(last=False)

    return tiered_compile(lower(), small, store), False


@dataclasses.dataclass(frozen=True)
class UnitProgram:
    """Host-side description of one unit's dataflow, ready to trace.

    ``kind`` is ``"query"`` (bare join result, used for views), ``"edges"``
    (query + src/dst edge projection) or ``"merged"`` (a JS-OJ group).
    ``capacities`` holds one static capacity per join step, in the exact
    order the traced function consumes them: main/S chain first, then per
    branch its inner chain followed by its outer-join attachment.
    """

    kind: str
    unit: object                          # JoinQuery | MergedQuery
    orders: Tuple[Tuple[str, ...], ...]   # (main,) or (S, branch, ...)
    capacities: Tuple[int, ...]
    inputs: Tuple[str, ...]               # base-table / view names read
    signature: object                     # hashable cache identity
    est_rows: Tuple[float, ...] = ()      # cost-model rows per join step


# ---------------------------------------------------------------------------
# Capacity planning
# ---------------------------------------------------------------------------

def _bucket(rows: float, margin: float, clamp: Optional[int]) -> int:
    cap = _round_capacity(int(rows * margin))
    if clamp is not None:
        cap = min(cap, max(8, clamp))
    return cap


def _query_inputs(query: JoinQuery) -> Tuple[str, ...]:
    return tuple(sorted({r.table for r in query.relations}))


def _merged_inputs(merged: MergedQuery) -> Tuple[str, ...]:
    names = {r.table for r in merged.pattern.relations}
    for b in merged.branches:
        names |= {r.table for r in b.relations}
    return tuple(sorted(names))


def build_query_program(
    db: Database, query: JoinQuery, edges: bool,
    margin: float = CAPACITY_MARGIN, clamp: Optional[int] = None,
) -> UnitProgram:
    """Pre-size a single query's join chain from the cost model."""
    est = estimate_query(db, query)
    rows = tuple(step_expansions(db, query, est.order))
    return UnitProgram(
        kind="edges" if edges else "query",
        unit=query,
        orders=(est.order,),
        capacities=tuple(_bucket(r, margin, clamp) for r in rows),
        inputs=_query_inputs(query),
        signature=("q", query_signature(query), edges),
        est_rows=rows,
    )


def build_merged_program(
    db: Database, merged: MergedQuery,
    margin: float = CAPACITY_MARGIN, clamp: Optional[int] = None,
) -> UnitProgram:
    """Pre-size a JS-OJ group: S chain, branch chains, outer attachments.

    Outer-join capacities follow Eq 3/4's expansion estimate but on the
    *first* link condition only (further conditions are post-filters of the
    static expansion, mirroring the executor's contract); the running row
    estimate between branches uses every condition.
    """
    sq = shared_query(merged)
    s_est = estimate_query(db, sq)
    orders: List[Tuple[str, ...]] = [s_est.order]
    cap_rows: List[float] = list(step_expansions(db, sq, s_est.order))
    rows = s_est.rows
    s_rel = s_est.to_rel()
    for b in merged.branches:
        if not b.relations:
            orders.append(())        # indicator-only branch: no join
            continue
        if len(b.relations) > 1:
            b_q = b.as_query()
            b_est = estimate_query(db, b_q)
            orders.append(b_est.order)
            cap_rows.extend(step_expansions(db, b_q, b_est.order))
            b_rel = b_est.to_rel()
        else:
            orders.append((b.relations[0].alias,))
            b_rel = scan_estimate(db, b.relations[0])
        sel_first = sel_all = 1.0
        for i, c in enumerate(b.link_conds):
            s = 1.0 / max(s_rel.col_ndv(c.left, c.lcol),
                          b_rel.col_ndv(c.right, c.rcol))
            if i == 0:
                sel_first = s
            sel_all *= s
        # unmatched left rows also occupy slots (counts = max(match, 1))
        cap_rows.append(rows * max(1.0, b_rel.rows * sel_first) + rows)
        rows *= max(1.0, b_rel.rows * sel_all)
    return UnitProgram(
        kind="merged",
        unit=merged,
        orders=tuple(orders),
        capacities=tuple(_bucket(r, margin, clamp) for r in cap_rows),
        inputs=_merged_inputs(merged),
        signature=("m", merged),
        est_rows=tuple(cap_rows),
    )


# ---------------------------------------------------------------------------
# Traced execution (runs under one jax.jit per unit)
# ---------------------------------------------------------------------------

def _scan(tables: Dict[str, Table], rel, needed=None) -> Table:
    """:func:`executor.scan_table` plus projection pushdown.

    ``needed`` (a set of qualified column names, or None for keep-all) drops
    every column the rest of the unit never references — scan filters are
    applied first, so filter columns need not survive the projection.
    Fewer columns means fewer gathers per join step: less to compile, less
    to move.  Runs under the ``scan:<alias>`` scope.
    """
    with jax.named_scope(f"scan:{rel.alias}"):
        t = scan_table(tables[rel.table], rel)
        if needed is not None:
            keep = [c for c in t.column_names() if c in needed]
            if keep and len(keep) < len(t.columns):
                t = t.select(keep)
    return t


def _needed_columns_query(query: JoinQuery) -> set:
    """Qualified columns a query's joins, post-filters, and outputs touch."""
    need = set()
    for c in query.conds:
        need.add(f"{c.left}.{c.lcol}")
        need.add(f"{c.right}.{c.rcol}")
    need.add(query.src.qualified())
    need.add(query.dst.qualified())
    return need


def _needed_columns_merged(merged: MergedQuery) -> set:
    need = _needed_columns_query(shared_query(merged))
    for b in merged.branches:
        for c in b.inner_conds + b.link_conds:
            need.add(f"{c.left}.{c.lcol}")
            need.add(f"{c.right}.{c.rcol}")
    for m in merged.members:
        for c in m.residual_conds:
            need.add(f"{c.left}.{c.lcol}")
            need.add(f"{c.right}.{c.rcol}")
        need.add(m.src.qualified())
        need.add(m.dst.qualified())
    return need


def _traced_query(
    tables: Dict[str, Table],
    query: JoinQuery,
    order: Sequence[str],
    caps_iter,
    totals: List[jax.Array],
    prefilter: List[jax.Array],
    use_kernel: bool,
    use_bloom: bool,
    needed=None,
) -> Table:
    """The executor's join chain, with static capacities and no host syncs.

    Same schedule as :func:`executor.execute_query` — both walk
    :func:`repro.core.model.join_schedule`, which is what keeps the
    pre-planned capacities aligned with the joins actually traced.  Each
    step runs under a ``jax.named_scope`` (``scan:<alias>``,
    ``join:<alias>``), which the compiled HLO keeps in its ops' metadata.
    """
    cur = _scan(tables, query.relation(order[0]), needed)
    for alias, conds, closing in join_schedule(query, order):
        nxt = _scan(tables, query.relation(alias), needed)
        on = [qualified_cond(c, alias) for c in conds]
        with jax.named_scope(f"join:{alias}"):
            cur, required, counts = join_with_capacity(
                cur, nxt, on, how="inner", capacity=next(caps_iter),
                use_kernel=use_kernel,
                bloom_bits=bloom_bits_for(nxt.capacity) if use_bloom else 0)
            for c in closing:
                cur = cur.mask(cur[f"{c.left}.{c.lcol}"]
                               == cur[f"{c.right}.{c.rcol}"])
        totals.append(required)
        if counts is not None:
            prefilter.append(counts)
    return cur


def _traced_merged(
    tables: Dict[str, Table],
    merged: MergedQuery,
    orders: Sequence[Tuple[str, ...]],
    caps_iter,
    totals: List[jax.Array],
    prefilter: List[jax.Array],
    use_kernel: bool,
    use_bloom: bool,
) -> Dict[str, Table]:
    """The executor's JS-OJ evaluation (Theorem 4.3), fully traced.

    Scopes as in :func:`_traced_query`, plus ``outer:<branch>`` for each
    branch's outer-join attachment and ``dedup:<member>`` for each
    member's row filter, dedup and edge projection.
    """
    needed = _needed_columns_merged(merged)
    cur = _traced_query(tables, shared_query(merged), orders[0], caps_iter,
                        totals, prefilter, use_kernel, use_bloom, needed)
    cur = cur.with_columns(
        __srow__=jnp.arange(cur.capacity, dtype=jnp.int32))
    indicators: Dict[str, str] = {}
    rowid_cols: Dict[str, str] = {}
    for bi, b in enumerate(merged.branches):
        ind = f"__m__{b.id}"
        indicators[b.id] = ind
        if not b.relations:
            with jax.named_scope(f"outer:{b.id}"):
                mask = jnp.ones((cur.capacity,), dtype=bool)
                for c in b.link_conds:
                    mask = mask & (cur[f"{c.left}.{c.lcol}"]
                                   == cur[f"{c.right}.{c.rcol}"])
                cur = cur.with_columns(**{ind: mask})
            continue
        if len(b.relations) > 1:
            branch_tbl = _traced_query(tables, b.as_query(), orders[1 + bi],
                                       caps_iter, totals, prefilter,
                                       use_kernel, use_bloom, needed)
        else:
            branch_tbl = _scan(tables, b.relations[0], needed)
        with jax.named_scope(f"outer:{b.id}"):
            brow = f"__brow__{b.id}"
            rowid_cols[b.id] = brow
            branch_tbl = branch_tbl.with_columns(
                **{brow: jnp.arange(branch_tbl.capacity, dtype=jnp.int32)})
            on = [(f"{c.left}.{c.lcol}", f"{c.right}.{c.rcol}")
                  for c in b.link_conds]
            cur, required, counts = left_outer_with_capacity(
                cur, branch_tbl, on, ind, capacity=next(caps_iter),
                use_kernel=use_kernel,
                bloom_bits=bloom_bits_for(branch_tbl.capacity)
                if use_bloom else 0)
        totals.append(required)
        if counts is not None:
            prefilter.append(counts)

    out: Dict[str, Table] = {}
    for m in merged.members:
        with jax.named_scope(f"dedup:{m.name}"):
            keep = jnp.ones((cur.capacity,), dtype=bool)
            for bid in m.branch_ids:
                keep = keep & cur[indicators[bid]]
            for c in m.residual_conds:
                keep = keep & (cur[f"{c.left}.{c.lcol}"]
                               == cur[f"{c.right}.{c.rcol}"])
            member_rows = cur.mask(keep)
            dedup_keys = ["__srow__"] + [
                rowid_cols[bid] for bid in m.branch_ids if bid in rowid_cols
            ]
            member_rows = dedup(member_rows, dedup_keys)
            out[m.name] = edge_output(member_rows, m.src, m.dst)
    return out


def _stack_totals(totals: List[jax.Array],
                  prefilter: List[jax.Array]) -> jax.Array:
    """The one vector the host syncs per attempt: each join step's
    required rows, then each Bloom prefilter's (probed, passed) pair."""
    parts = [t.astype(jnp.int32).reshape(-1) for t in totals + prefilter]
    if not parts:
        return jnp.zeros((0,), jnp.int32)
    return jnp.concatenate(parts)


def _unit_function_name(program: UnitProgram) -> str:
    """``unit_<label>``: the jitted function's name, so the unit's HLO
    module is ``jit_unit_<label>``.  The label is the edge label, the
    view's name, or the merged members' labels joined by ``_``."""
    unit = program.unit
    label = ("_".join(m.name for m in unit.members)
             if program.kind == "merged" else unit.name)
    return "unit_" + re.sub(r"[^0-9A-Za-z_]", "_", label)


# the named scopes the traced steps run under (see _traced_merged)
_STEP_SCOPES = ("scan:", "join:", "outer:", "dedup:")
_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _step_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction: step scope}) of one compiled text."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    scopes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        inst = _INSTRUCTION.match(line)
        meta = inst and _OP_NAME.search(line)
        if meta:
            scope = next((seg for seg in meta.group(1).split("/")[1:]
                          if seg.startswith(_STEP_SCOPES)), None)
            if scope is not None:
                scopes[inst.group(1)] = scope
    return module, scopes


def op_scopes() -> Dict[str, Dict[str, str]]:
    """Plan step of each device op: ``{HLO module: {instruction: scope}}``.

    Read from the compiled text of every cached unit executable (module
    ``jit_unit_<label>``), whose ops keep the ``jax.named_scope`` of their
    traced step in their ``op_name`` metadata.  A profile names its device
    ops by module and instruction, so this maps each to ``join:<alias>``,
    ``outer:<branch>``, ...  An instruction that two executables of one
    module place under different scopes is left out.  Parses every text
    on each call: for reading a profile, never on a request's path.
    """
    with _CACHE_LOCK:
        executables = list(_EXECUTABLE_CACHE.values())
    out: Dict[str, Dict[str, str]] = {}
    clashes: Dict[str, set] = collections.defaultdict(set)
    for exe in executables:
        module, scopes = _step_scopes(exe.as_text())
        known = out.setdefault(module, {})
        for inst, scope in scopes.items():
            if known.get(inst, scope) != scope:
                clashes[module].add(inst)
            known[inst] = scope
    for module, insts in clashes.items():
        for inst in insts:
            del out[module][inst]
    return out


def _make_fn(program: UnitProgram, use_kernel: bool, use_bloom: bool):
    if program.kind == "merged":
        def fn(tables):
            totals: List[jax.Array] = []
            prefilter: List[jax.Array] = []
            edges = _traced_merged(tables, program.unit, program.orders,
                                   iter(program.capacities), totals,
                                   prefilter, use_kernel, use_bloom)
            return edges, _stack_totals(totals, prefilter)
    else:
        # views ("query") keep every column — later queries are rewritten
        # over them and may reference any of it; edge units only carry what
        # their conditions and outputs touch
        needed = (_needed_columns_query(program.unit)
                  if program.kind == "edges" else None)

        def fn(tables):
            totals: List[jax.Array] = []
            prefilter: List[jax.Array] = []
            res = _traced_query(tables, program.unit, program.orders[0],
                                iter(program.capacities), totals, prefilter,
                                use_kernel, use_bloom, needed)
            if program.kind == "edges":
                res = edge_output(res, program.unit.src, program.unit.dst)
            return res, _stack_totals(totals, prefilter)
    fn.__name__ = fn.__qualname__ = _unit_function_name(program)
    return fn


# ---------------------------------------------------------------------------
# Compiler / executable cache
# ---------------------------------------------------------------------------

def _schema_fp(inputs: Dict[str, Table]) -> Tuple:
    """Hashable shape+dtype fingerprint of the unit's input tables."""
    return tuple(sorted(
        (name, t.capacity,
         tuple((c, str(t[c].dtype)) for c in t.column_names()))
        for name, t in inputs.items()))


def _bloom_counter(outcome: str):
    return obs.REGISTRY.counter(
        "pipeline_bloom_rows_total",
        help="Probe rows with a key before (probed) and after (passed) the "
             "Bloom semi-join prefilter.",
        outcome=outcome)


class PipelineCompiler:
    """Compiles plan units into cached, overflow-safe jitted executables.

    One instance is typically owned by an
    :class:`repro.api.ExtractionEngine`; sharing an instance across engines
    (or passing one explicitly) shares the per-unit capacity memory, while
    the compiled executables themselves live in a process-wide
    content-addressed store, so *any* compiler benefits from *any* prior
    compilation of the same (signature, capacities, schema) unit.

    ``use_kernel=None`` auto-selects the Pallas kernels on TPU and the jnp
    paths elsewhere (:func:`repro.kernels.ops.resolve_use_kernel`):
    ``use_bloom`` (default: follows ``use_kernel``) prunes probe rows with
    the ``bloom`` semi-join prefilter kernel before each capacity
    expansion, and ``probe_kernel`` (on only when ``use_kernel=True``)
    routes the join probe through ``sorted_probe``.
    ``initial_capacity_clamp`` caps the *initial* capacity buckets —
    production code never sets it; tests use it to force the overflow-retry
    branch.
    """

    def __init__(self, margin: float = CAPACITY_MARGIN,
                 use_kernel: Optional[bool] = None,
                 use_bloom: Optional[bool] = None,
                 max_programs: int = 256,
                 max_retries: Optional[int] = None,
                 initial_capacity_clamp: Optional[int] = None,
                 tier_compile: bool = True):
        self.margin = float(margin)
        self.use_kernel = resolve_use_kernel(use_kernel)
        self.probe_kernel = resolve_use_kernel(use_kernel, probe=True)
        self.use_bloom = self.use_kernel if use_bloom is None \
            else bool(use_bloom)
        self.max_programs = max_programs
        self.max_retries = max_retries
        self.initial_capacity_clamp = initial_capacity_clamp
        self.tier_compile = bool(tier_compile)
        # guards stats and _programs: the background re-optimization thread
        # bumps counters, and a shared compiler may serve several engines
        self._lock = threading.Lock()
        self._programs: "collections.OrderedDict" = collections.OrderedDict()
        # stats-independent program memo keyed by (kind, unit): when a
        # unit's stats fingerprint changes (incremental refresh mutates
        # tables every round, so _programs misses every round), the unit
        # keeps its previously learned join orders and capacities instead
        # of re-estimating — jittering estimates would flip orders and
        # capacity buckets, recompiling a fresh executable per refresh.
        # Overflow-retry still grows capacities when the data truly
        # outgrows them, and updates this memo too.
        self._unit_memo: "collections.OrderedDict" = collections.OrderedDict()
        self.max_unit_memo = 512
        # last observed per-step actual rows, by program signature: the
        # host-side values the overflow check already synced.  EXPLAIN
        # ANALYZE reads them back, so reporting estimated-vs-actual rows
        # adds zero device round-trips to the hot path.
        self._last_rows: "collections.OrderedDict" = collections.OrderedDict()
        self.max_last_rows = 512
        self.stats = {"hits": 0, "misses": 0, "retries": 0,
                      "compiled": 0, "compile_s": 0.0,
                      "tiered": 0, "reoptimized": 0,
                      "bloom_probed": 0, "bloom_passed": 0}
        # exported at 0 from the first compiler on, so a reader can tell a
        # path without a prefilter (nothing probed) from a process that
        # never ran a pipeline
        for outcome in ("probed", "passed"):
            _bloom_counter(outcome)

    _EVENT_METRIC = "pipeline_executable_events_total"

    def _bump(self, key: str, amount=1) -> None:
        with self._lock:
            self.stats[key] += amount
        obs.REGISTRY.counter(
            self._EVENT_METRIC,
            help="Executable-cache and retry events by kind.",
            event=key).inc(amount)

    # -- bookkeeping ---------------------------------------------------------
    def clear(self) -> None:
        """Forget programs and proven capacities (keeps the global
        executable store; see :func:`clear_executable_cache`)."""
        with self._lock:
            self._programs.clear()
            self._unit_memo.clear()

    def _remember_unit(self, kind: str, unit, prog: UnitProgram) -> None:
        with self._lock:
            self._unit_memo[(kind, unit)] = prog
            self._unit_memo.move_to_end((kind, unit))
            while len(self._unit_memo) > self.max_unit_memo:
                self._unit_memo.popitem(last=False)

    def cache_info(self) -> Dict[str, float]:
        with self._lock:
            return {"programs": len(self._programs),
                    "executables": len(_EXECUTABLE_CACHE), **self.stats}

    # -- public execution entry points --------------------------------------
    def run_query(self, db: Database, query: JoinQuery) -> Table:
        """Execute a join query as one fused executable (no projection)."""
        return self._run(db, *self._program(db, "query", query))

    def run_query_edges(self, db: Database, query: JoinQuery) -> Table:
        """Execute a query and project it down to its (src, dst) edges."""
        return self._run(db, *self._program(db, "edges", query))

    def run_merged(self, db: Database,
                   merged: MergedQuery) -> Dict[str, Table]:
        """Execute a JS-OJ group; returns {edge label: edge table}."""
        return self._run(db, *self._program(db, "merged", merged))

    # -- internals -----------------------------------------------------------
    def _stats_fp(self, db: Database, inputs: Sequence[str]) -> Tuple:
        return tuple((n, db.stats[n].fingerprint()) for n in inputs)

    def _program(self, db: Database, kind: str, unit):
        inputs = (_merged_inputs(unit) if kind == "merged"
                  else _query_inputs(unit))
        pkey = (kind, unit, self._stats_fp(db, inputs))
        with self._lock:
            prog = self._programs.get(pkey)
            if prog is not None:
                self._programs.move_to_end(pkey)
                return pkey, prog
        with self._lock:
            prog = self._unit_memo.get((kind, unit))
        if prog is None:
            if kind == "merged":
                prog = build_merged_program(db, unit, self.margin,
                                            self.initial_capacity_clamp)
            else:
                prog = build_query_program(db, unit, edges=(kind == "edges"),
                                           margin=self.margin,
                                           clamp=self.initial_capacity_clamp)
            self._remember_unit(kind, unit, prog)
        with self._lock:
            self._programs[pkey] = prog
            while len(self._programs) > self.max_programs:
                self._programs.popitem(last=False)
        return pkey, prog

    def _executable(self, prog: UnitProgram, inputs: Dict[str, Table]):
        key = (prog.signature, prog.orders, prog.capacities,
               self.probe_kernel, self.use_bloom, _schema_fp(inputs))
        tiered = (self.tier_compile
                  and max(prog.capacities, default=0) <= TIER_MAX_CAPACITY)

        def lower():
            fn = _make_fn(prog, self.probe_kernel, self.use_bloom)
            return jax.jit(fn).lower(inputs)

        t0 = time.perf_counter()
        exe, hit = cached_tiered_compile(
            _EXECUTABLE_CACHE, _CACHE_LOCK, key, lower, tiered,
            _EXECUTABLE_CACHE_SIZE,
            on_reoptimized=lambda: self._bump("reoptimized"))
        if hit:
            self._bump("hits")
            return exe
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["misses"] += 1
            self.stats["compile_s"] += dt
            self.stats["compiled"] += 1
            if tiered:
                self.stats["tiered"] += 1
        obs.REGISTRY.counter(self._EVENT_METRIC, event="misses").inc()
        if tiered:
            obs.REGISTRY.counter(self._EVENT_METRIC, event="tiered").inc()
        obs.REGISTRY.histogram(
            "pipeline_compile_seconds",
            help="Per-unit XLA trace+compile wall time.",
            kind=prog.kind).observe(dt)
        obs.TRACER.record(f"pipeline.compile:{prog.kind}", t0, t0 + dt,
                          category="compile", detail=True,
                          capacities=list(prog.capacities), tiered=tiered)
        return exe

    def _observe_rows(self, prog: UnitProgram, caps: Tuple[int, ...],
                      need: np.ndarray) -> None:
        """Predicted-vs-actual row accounting (host-known values only).

        ``need`` was already synced by the overflow check, so this adds no
        device round-trips.  The estimate ratio is (actual+1)/(predicted+1)
        — log₂ buckets make under- and over-estimates symmetric around 1 —
        and utilization is actual/capacity (1.0 = a bucket about to
        overflow).  The per-step values are also retained by program
        signature for :meth:`last_rows` (EXPLAIN ANALYZE).
        """
        if need.size == 0:
            return
        ratio_h = obs.REGISTRY.histogram(
            "pipeline_rows_estimate_ratio",
            help="Actual/predicted rows per join step (1 = perfect "
                 "cost-model estimate).", kind=prog.kind)
        actual = [int(n) for n in need.tolist()]
        for i, n in enumerate(actual):
            if i < len(prog.est_rows):
                ratio_h.observe((n + 1.0) / (prog.est_rows[i] + 1.0))
        with self._lock:
            self._last_rows[prog.signature] = {
                "actual": actual,
                "capacities": [int(c) for c in caps],
                "est_rows": [float(r) for r in prog.est_rows],
            }
            self._last_rows.move_to_end(prog.signature)
            while len(self._last_rows) > self.max_last_rows:
                self._last_rows.popitem(last=False)

    def _count_prefilter(self, pairs: np.ndarray) -> None:
        """Bloom prefilter rows of one kept attempt: probe rows with a key
        before the filter (``probed``) and after it (``passed``), summed
        over the unit's joins.  Host values from the one sync."""
        if pairs.size == 0:
            return
        probed, passed = (int(x) for x in pairs.reshape(-1, 2).sum(axis=0))
        with self._lock:
            self.stats["bloom_probed"] += probed
            self.stats["bloom_passed"] += passed
        _bloom_counter("probed").inc(probed)
        _bloom_counter("passed").inc(passed)

    def last_rows(self, signature) -> Optional[Dict[str, list]]:
        """Per-step ``{actual, capacities, est_rows}`` from the most recent
        run of the program with this signature, or ``None`` if it never ran
        (or aged out of the bounded retention window).  Pure host memory —
        reading it performs no device work."""
        with self._lock:
            rec = self._last_rows.get(signature)
            return None if rec is None else {k: list(v)
                                             for k, v in rec.items()}

    def peek_program(self, db: Database, kind: str, unit):
        """The program a unit *would* run with — read-only introspection.

        Resolution mirrors :meth:`_program` (stats-keyed programs first,
        then the stats-independent memo with its proven capacities), but a
        miss builds a fresh cost-model program WITHOUT entering it into
        either cache: EXPLAIN over estimated view stats must not pin
        estimate-derived capacities into the memo the execution path will
        later trust.  Returns ``(program, source)`` with source one of
        ``"programs"`` | ``"memo"`` | ``"estimated"``.
        """
        inputs = (_merged_inputs(unit) if kind == "merged"
                  else _query_inputs(unit))
        pkey = (kind, unit, self._stats_fp(db, inputs))
        with self._lock:
            prog = self._programs.get(pkey)
            if prog is not None:
                return prog, "programs"
            prog = self._unit_memo.get((kind, unit))
            if prog is not None:
                return prog, "memo"
        if kind == "merged":
            prog = build_merged_program(db, unit, self.margin,
                                        self.initial_capacity_clamp)
        else:
            prog = build_query_program(db, unit, edges=(kind == "edges"),
                                       margin=self.margin,
                                       clamp=self.initial_capacity_clamp)
        return prog, "estimated"

    def executable_state(self, prog: UnitProgram,
                         tables: Dict[str, Table]) -> str:
        """Would running this program compile or just launch?

        ``"cached"`` — an executable for the exact (signature, orders,
        capacities, kernel flags, schema) key is resident; ``"uncompiled"``
        — it would compile on first run; ``"unknown"`` — an input (an
        unmaterialized view) is missing from ``tables``, so the schema part
        of the key cannot be formed without executing.
        """
        if any(n not in tables for n in prog.inputs):
            return "unknown"
        inputs = {n: tables[n] for n in prog.inputs}
        key = (prog.signature, prog.orders, prog.capacities,
               self.probe_kernel, self.use_bloom, _schema_fp(inputs))
        with _CACHE_LOCK:
            return "cached" if key in _EXECUTABLE_CACHE else "uncompiled"

    def _run(self, db: Database, pkey, prog: UnitProgram):
        """Execute with overflow-retry; remembers proven capacities.

        One host sync per attempt (the totals vector).  An overflowed step
        re-executes at the pow-2 bucket of its *exact* requirement, which at
        least doubles it; steps downstream of a truncation may only reveal
        their true requirement on the retry, so the loop runs to a fixpoint
        (bounded by the step count — each round fixes at least the first
        overflowing step for good).  The synced vector also carries each
        Bloom prefilter's (probed, passed) rows, counted on the attempt
        that is kept (:meth:`_count_prefilter`).
        """
        inputs = {n: db.tables[n] for n in prog.inputs}
        caps = prog.capacities
        attempts = self.max_retries
        if attempts is None:
            attempts = max(8, len(caps) + 1)
        for _ in range(attempts + 1):
            cur = dataclasses.replace(prog, capacities=caps)
            exe = self._executable(cur, inputs)
            with obs.span("pipeline.dispatch", category="execute",
                          detail=True, kind=prog.kind):
                out, totals = exe(inputs)
            # the host only blocks here while the device runs the unit
            with obs.span("pipeline.wait", category="execute", detail=True):
                synced = np.asarray(totals)           # the one host sync
            need, prefilter = synced[:len(caps)], synced[len(caps):]
            if need.size == 0 or bool(
                    (need <= np.asarray(caps, dtype=np.int64)).all()):
                self._observe_rows(prog, caps, need)
                self._count_prefilter(prefilter)
                if caps != prog.capacities:
                    with self._lock:                  # skip retries next time
                        self._programs[pkey] = cur
                    # stats-independent memo too: future rebuilds of this
                    # unit (new stats fingerprints) start at the proven
                    # capacities instead of re-learning them via retries
                    self._remember_unit(prog.kind, prog.unit, cur)
                return out
            self._bump("retries")
            caps = tuple(
                _round_capacity(int(n)) if int(n) > c else c
                for n, c in zip(need.tolist(), caps))
        raise RuntimeError(
            f"pipeline overflow retry did not converge for "
            f"{prog.signature!r} (capacities {caps})")
