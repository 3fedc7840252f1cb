"""Static lint for ExecutionPlan / ShardedPlan JSON artifacts (RPL0xx).

``ExecutionPlan.from_dict`` checks the schema version and field *presence*
— by design it stays permissive about values, because a plan that parses
is still just a suggestion until ``bind`` meets a concrete matrix.  But a
fleet replaying :class:`~repro.core.plan_store.PlanStore` artifacts wants
infeasible geometry rejected *before* any launch (the paper's whole
premise, applied to the artifact itself): a mis-aligned tile or an
under-provisioned slab bound is knowable from the JSON alone.

This module lints the raw payload dict — **no jax import, no bind, no
repro.core import** — so the same checks run in the jax-free CLI
(``python -m repro.analyze lint-plan``), inside ``PlanStore`` loads
(errors quarantine with reason ``"lint"``), at
``SpMVService.register(strict_lint=)``, and as the ``Planner``'s
self-check on every plan it mints.  The structural constants here
(geometry knobs, 8-alignment, slab arithmetic, recipe defaults) mirror
``core/kernel_tune.py`` / ``kernels/ops.py``; the registry audit and
tests keep them from drifting.

Rule catalog (docs/analysis.md):

  RPL001  schema shape: required/unknown fields, types, schema_version
  RPL002  TileGeometry: unknown knobs, positivity, 8-alignment
  RPL003  CSR slab-coverage bound vs the static lower bound implied by
          the recorded fingerprint
  RPL004  per-(format, op) geometry-driven VMEM footprint vs budget
  RPL005  SELL bucket table vs the transform recipe (width quantum,
          duplicate widths, bucket count vs slice_rows)
  RPL006  hybrid block structure: contiguous cover from row 0, last end
          == fingerprint n, no nested hybrid, per-block fingerprints
  RPL007  sharded partition: shard spans contiguous, row-axis spans sum
          to nrows, per-shard fingerprints present, nnz conservation,
          mesh shape
  RPL008  transform recipe: name matches fmt, param types
  RPL009  fingerprint self-consistency (mu ~ nnz/n, d_mat ~ sigma/mu)
  RPL010  streaming artifacts (repro.stream): DeltaBatch JSON bounds
          and stream_plan envelopes (nested plan lint, policy ranges,
          sketch consistency)
"""
from __future__ import annotations

import hashlib
import json
from typing import Any, Dict, List, Optional

from .findings import ERROR, WARN, Finding

#: ceiling for the geometry-driven VMEM working set (RPL004): the
#: compiler's default scoped-VMEM limit on a TPU v5e core, which is the
#: limit every kernel in ``repro.kernels`` compiles under (none raises
#: it).  ``tests/test_chip_compile.py`` compiles every tuner candidate
#: for a described v5e against that limit.
DEFAULT_VMEM_BUDGET = 16 * 2 ** 20

#: mirrors core.plan.SCHEMA_VERSION / SHARDED_SCHEMA_VERSION (the
#: registry audit's job is to notice if these ever drift)
SCHEMA_VERSION = 1
SHARDED_SCHEMA_VERSION = 1
#: mirrors stream.delta.DELTA_SCHEMA_VERSION /
#: stream.drift.STREAM_PLAN_SCHEMA_VERSION (same drift discipline)
DELTA_SCHEMA_VERSION = 1
STREAM_PLAN_SCHEMA_VERSION = 1

KNOWN_FORMATS = ("csr", "ccs", "coo_row", "coo_col", "ell_row", "ell_col",
                 "sell", "bcsr", "hybrid")
KNOWN_OPS = ("spmv", "spmm")
KNOWN_TIERS = ("reference", "kernel")

GEOM_KNOBS = ("block_rows", "block_w", "block_k", "block_nnz",
              "slabs_per_block")
#: knobs each format's kernel wrappers actually read (kernels/ops.py);
#: formats without a kernel read none
_FMT_KNOBS = {
    "ell_row": {"block_rows", "block_w", "block_k"},
    "ell_col": {"block_rows", "block_w", "block_k"},
    "sell": {"block_rows", "block_w", "block_k"},
    "csr": {"block_rows", "block_nnz", "block_k", "slabs_per_block"},
    "coo_row": set(), "coo_col": set(), "ccs": set(), "bcsr": set(),
}
#: wrapper defaults used when a knob is absent (kernels/ops.py)
_DEFAULT_BR = 256
_DEFAULT_BN = 2048
_DEFAULT_BW = 128
_DEFAULT_BK = 128

_EXEC_KEYS = {"schema_version", "fmt", "rule", "tier", "batch",
              "expected_iterations", "transform", "geometry", "machine",
              "d_mat", "d_star", "expected_gain", "fingerprint", "blocks"}
_EXEC_REQUIRED = ("schema_version", "fmt", "rule", "tier", "batch",
                  "expected_iterations", "transform", "geometry")
_SHARDED_KEYS = {"kind", "schema_version", "axis", "strategy", "params",
                 "mesh_shape", "mesh_axis", "batch", "shards",
                 "fingerprint"}
_FP_KEYS = ("n", "nnz", "mu", "sigma", "d_mat", "sig")


def _ceil(a: int, b: int) -> int:
    return -(-int(a) // max(int(b), 1))


def _align8(n: int) -> int:
    return max(8, 8 * ((int(n) + 7) // 8))


def _is_int(v: Any) -> bool:
    return isinstance(v, int) and not isinstance(v, bool)


def _is_num(v: Any) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


class _Lint:
    def __init__(self, vmem_budget: int):
        self.vmem_budget = int(vmem_budget)
        self.findings: List[Finding] = []

    def add(self, rule: str, severity: str, where: str, msg: str) -> None:
        self.findings.append(Finding(rule=rule, severity=severity,
                                     message=msg, where=where))

    def err(self, rule: str, where: str, msg: str) -> None:
        self.add(rule, ERROR, where, msg)

    def warn(self, rule: str, where: str, msg: str) -> None:
        self.add(rule, WARN, where, msg)

    # -- fingerprint (RPL009) ------------------------------------------------
    def fingerprint(self, fp: Any, where: str) -> Optional[Dict[str, Any]]:
        """Validate a fingerprint dict; returns it when structurally
        usable (n/nnz ints) so callers can cross-check against it."""
        w = f"{where}fingerprint"
        if not isinstance(fp, dict):
            self.err("RPL001", w, f"fingerprint must be an object; got "
                                  f"{type(fp).__name__}")
            return None
        for k in fp:
            if k not in _FP_KEYS:
                self.warn("RPL001", w, f"unknown fingerprint field {k!r}")
        for k in ("n", "nnz", "sig"):
            if not _is_int(fp.get(k)):
                self.err("RPL009", w, f"fingerprint.{k} must be an "
                                      f"integer; got {fp.get(k)!r}")
                return None
        n, nnz = fp["n"], fp["nnz"]
        if n < 0 or nnz < 0:
            self.err("RPL009", w, f"fingerprint has negative dimensions "
                                  f"(n={n}, nnz={nnz})")
            return None
        if nnz > 0 and n == 0:
            self.err("RPL009", w, f"nnz={nnz} with n=0 rows")
            return None
        for k in ("mu", "sigma", "d_mat"):
            v = fp.get(k)
            if v is not None and not _is_num(v):
                self.err("RPL009", w, f"fingerprint.{k} must be a number "
                                      f"or null; got {v!r}")
        mu = fp.get("mu")
        if _is_num(mu) and n > 0:
            expect = nnz / n
            if abs(mu - expect) > 1e-6 * max(1.0, expect):
                self.warn("RPL009", w, f"mu={mu:g} but nnz/n={expect:g}")
        sigma, d_mat = fp.get("sigma"), fp.get("d_mat")
        if _is_num(mu) and _is_num(sigma) and _is_num(d_mat) and mu > 0:
            expect = sigma / mu
            if abs(d_mat - expect) > 1e-6 * max(1.0, expect):
                self.warn("RPL009", w,
                          f"d_mat={d_mat:g} but sigma/mu={expect:g}")
        return fp

    # -- geometry (RPL002) ---------------------------------------------------
    def _knobs(self, gd: Dict[str, Any], fmt: str, where: str,
               allow_buckets: bool) -> None:
        relevant = _FMT_KNOBS.get(fmt, set(GEOM_KNOBS))
        for k, v in gd.items():
            if k == "buckets":
                if not allow_buckets:
                    self.warn("RPL002", where, "per-bucket table on a "
                                               "non-SELL geometry")
                self._buckets(v, where)
                continue
            if k not in GEOM_KNOBS:
                self.err("RPL002", where, f"unknown geometry field {k!r}")
                continue
            if not _is_int(v) or v < 1:
                self.err("RPL002", where,
                         f"{k}={v!r} must be a positive integer")
                continue
            if k != "slabs_per_block" and v % 8:
                self.err("RPL002", where, f"{k}={v} is not 8-aligned")
            if k not in relevant:
                self.warn("RPL002", where,
                          f"{k} is not used by the {fmt!r} kernels")

    def _buckets(self, buckets: Any, where: str) -> List[int]:
        w = f"{where}.buckets"
        if not isinstance(buckets, list):
            self.err("RPL002", w, f"buckets must be a list; got "
                                  f"{type(buckets).__name__}")
            return []
        widths: List[int] = []
        for i, pair in enumerate(buckets):
            if (not isinstance(pair, (list, tuple)) or len(pair) != 2
                    or not _is_int(pair[0]) or pair[0] < 1
                    or not isinstance(pair[1], dict)):
                self.err("RPL002", f"{w}[{i}]",
                         "bucket entries must be [width, geometry] pairs")
                continue
            widths.append(pair[0])
            self._knobs(pair[1], "sell", f"{w}[{i}]", allow_buckets=False)
        return widths

    def geometry(self, geo: Any, fmt: str, where: str,
                 fp: Optional[Dict[str, Any]], tier: str,
                 params: Dict[str, Any], batch: int) -> None:
        w = f"{where}geometry"
        if not isinstance(geo, dict):
            self.err("RPL001", w, f"geometry must be an object; got "
                                  f"{type(geo).__name__}")
            return
        for op, gd in geo.items():
            wo = f"{w}.{op}"
            if op not in KNOWN_OPS:
                self.err("RPL002", wo,
                         f"unknown op {op!r}; one of {KNOWN_OPS}")
            if not isinstance(gd, dict):
                self.err("RPL002", wo, f"op geometry must be an object; "
                                       f"got {type(gd).__name__}")
                continue
            if fmt == "hybrid":
                self.warn("RPL006", wo, "hybrid plans carry geometry on "
                                        "their block sub-plans, not at "
                                        "the top level")
                continue
            self._knobs(gd, fmt, wo, allow_buckets=(fmt == "sell"))
            self._slab_bound(gd, fmt, wo, fp, params)
            if tier == "kernel":
                self._vmem(gd, fmt, op, wo, params, batch)

    # -- slab bound (RPL003) -------------------------------------------------
    def _slab_bound(self, gd: Dict[str, Any], fmt: str, where: str,
                    fp: Optional[Dict[str, Any]],
                    params: Dict[str, Any]) -> None:
        spb = gd.get("slabs_per_block")
        if not _is_int(spb) or fmt != "csr":
            return
        if fp is None:
            self.warn("RPL003", where, "slabs_per_block recorded but the "
                                       "plan has no fingerprint to check "
                                       "it against")
            return
        n, nnz = fp["n"], fp["nnz"]
        br = gd.get("block_rows") or _DEFAULT_BR
        bn = gd.get("block_nnz") or _DEFAULT_BN
        if not _is_int(br) or not _is_int(bn) or br < 1 or bn < 1:
            return                      # RPL002 already reported
        segments = _ceil(n, br)                  # row tiles
        units = nnz
        # every launch sweeps segments * spb slabs of bn units each; the
        # recorded structure needs at least ceil(units / (segments * bn))
        # slabs per segment block no matter how the rows distribute
        need = max(1, _ceil(units, max(segments, 1) * bn)) if units else 1
        if spb < need:
            self.err("RPL003", where,
                     f"slabs_per_block={spb} cannot cover the recorded "
                     f"structure: n={n}, nnz={nnz} needs at least {need} "
                     f"slabs per block at block_rows={br}, block_nnz={bn}")

    # -- VMEM footprint (RPL004) ----------------------------------------------
    def _vmem(self, gd: Dict[str, Any], fmt: str, op: str, where: str,
              params: Dict[str, Any], batch: int) -> None:
        size = _footprint(gd, fmt, op, params, batch)
        if size is not None and size > self.vmem_budget:
            self.err("RPL004", where,
                     f"geometry-driven VMEM footprint ~{size / 2**20:.1f} "
                     f"MiB exceeds the {self.vmem_budget / 2**20:.0f} MiB "
                     f"budget")

    # -- SELL recipe vs bucket table (RPL005) ----------------------------------
    def _sell(self, d: Dict[str, Any], where: str,
              fp: Optional[Dict[str, Any]]) -> None:
        params = _params_of(d)
        quantum = params.get("width_quantum", 8)
        slice_rows = params.get("slice_rows", 128)
        if not _is_int(quantum) or quantum < 1:
            self.err("RPL008", f"{where}transform",
                     f"width_quantum={quantum!r} must be a positive "
                     f"integer")
            quantum = 8
        if not _is_int(slice_rows) or slice_rows < 1:
            self.err("RPL008", f"{where}transform",
                     f"slice_rows={slice_rows!r} must be a positive "
                     f"integer")
            slice_rows = 128
        geo = d.get("geometry")
        if not isinstance(geo, dict):
            return
        for op, gd in geo.items():
            if not isinstance(gd, dict) or "buckets" not in gd:
                continue
            w = f"{where}geometry.{op}.buckets"
            widths = [p[0] for p in gd["buckets"]
                      if isinstance(p, (list, tuple)) and len(p) == 2
                      and _is_int(p[0])]
            seen = set()
            for wd in widths:
                if wd % quantum:
                    self.err("RPL005", w,
                             f"bucket width {wd} is not a multiple of the "
                             f"recipe's width_quantum={quantum}")
                if wd in seen:
                    self.err("RPL005", w, f"duplicate bucket width {wd}")
                seen.add(wd)
            if any(b > a for a, b in zip(widths, widths[1:])):
                self.warn("RPL005", w,
                          "bucket widths are not sorted descending (the "
                          "transform emits them widest-first)")
            if fp is not None and widths:
                max_buckets = max(1, _ceil(fp["n"], slice_rows))
                if len(widths) > max_buckets:
                    self.err("RPL005", w,
                             f"{len(widths)} buckets but slice_rows="
                             f"{slice_rows} over n={fp['n']} rows yields "
                             f"at most {max_buckets}")

    # -- transform recipe (RPL008) ---------------------------------------------
    def transform(self, d: Dict[str, Any], fmt: str, where: str) -> None:
        t = d.get("transform")
        w = f"{where}transform"
        if not isinstance(t, dict) or not isinstance(t.get("name"), str):
            self.err("RPL001", w, "transform must be an object with a "
                                  "string 'name'")
            return
        name = t["name"]
        params = t.get("params", {})
        if not isinstance(params, dict):
            self.err("RPL001", w, f"transform.params must be an object; "
                                  f"got {type(params).__name__}")
            return
        if name not in KNOWN_FORMATS:
            self.err("RPL008", w, f"unknown transform {name!r}; one of "
                                  f"{KNOWN_FORMATS}")
        elif name != fmt:
            self.err("RPL008", w,
                     f"transform {name!r} cannot produce fmt {fmt!r} — "
                     f"bind would dispatch the wrong container")
        if name == "bcsr":
            b = params.get("block", 8)
            if not _is_int(b) or b < 1:
                self.err("RPL008", w, f"block={b!r} must be a positive "
                                      f"integer")
        if name in ("csr", "ccs", "coo_row", "coo_col") and params:
            self.warn("RPL008", w,
                      f"the {name!r} transform takes no params; got "
                      f"{sorted(params)}")

    # -- whole plans -----------------------------------------------------------
    def exec_plan(self, d: Dict[str, Any], where: str,
                  allow_hybrid: bool = True) -> Optional[Dict[str, Any]]:
        """Lint one ExecutionPlan payload; returns its fingerprint dict
        (when usable) so containers can cross-check partitions."""
        for k in d:
            if k not in _EXEC_KEYS:
                self.warn("RPL001", f"{where}{k}", "unknown plan field")
        missing = [k for k in _EXEC_REQUIRED if k not in d]
        if missing:
            self.err("RPL001", where or "plan",
                     f"missing required fields {missing}")
            return None
        if d["schema_version"] != SCHEMA_VERSION:
            self.err("RPL001", f"{where}schema_version",
                     f"unsupported schema_version={d['schema_version']!r};"
                     f" this linter reads version {SCHEMA_VERSION}")
        fmt = d["fmt"]
        if not isinstance(fmt, str) or fmt not in KNOWN_FORMATS:
            self.err("RPL001", f"{where}fmt",
                     f"unknown format {fmt!r}; one of {KNOWN_FORMATS}")
            return None
        if d["tier"] not in KNOWN_TIERS:
            self.err("RPL001", f"{where}tier",
                     f"unknown tier {d['tier']!r}; one of {KNOWN_TIERS}")
        if not isinstance(d["rule"], str):
            self.err("RPL001", f"{where}rule", "rule must be a string")
        batch = d["batch"]
        if not _is_int(batch) or batch < 1:
            self.err("RPL001", f"{where}batch",
                     f"batch={batch!r} must be a positive integer")
            batch = 1
        k_iter = d["expected_iterations"]
        if not _is_int(k_iter) or k_iter < 1:
            self.err("RPL001", f"{where}expected_iterations",
                     f"expected_iterations={k_iter!r} must be a positive "
                     f"integer")
        for key in ("d_mat", "d_star", "expected_gain"):
            v = d.get(key)
            if v is not None and not _is_num(v):
                self.err("RPL001", f"{where}{key}",
                         f"must be a number or null; got {v!r}")

        fp = None
        if d.get("fingerprint") is not None:
            fp = self.fingerprint(d["fingerprint"], where)
        self.transform(d, fmt, where)
        tier = d["tier"] if d["tier"] in KNOWN_TIERS else "reference"
        self.geometry(d.get("geometry"), fmt, where, fp, tier,
                      _params_of(d), batch)
        if fmt == "sell":
            self._sell(d, where, fp)

        blocks = d.get("blocks")
        if fmt == "hybrid":
            if not allow_hybrid:
                self.err("RPL006", where or "plan",
                         "hybrid plans cannot nest inside hybrid blocks")
            if not isinstance(blocks, list) or not blocks:
                self.err("RPL006", where or "plan",
                         "hybrid plan has no blocks")
                return fp
            self._hybrid_blocks(blocks, where, fp)
        elif blocks:
            self.err("RPL006", f"{where}blocks",
                     f"leaf plan (fmt={fmt!r}) carries hybrid blocks")
        return fp

    def _hybrid_blocks(self, blocks: List[Any], where: str,
                       fp: Optional[Dict[str, Any]]) -> None:
        prev_end, nnz_sum, all_fp = 0, 0, True
        for i, blk in enumerate(blocks):
            w = f"{where}blocks[{i}]"
            if not isinstance(blk, dict) or "rows" not in blk \
                    or "plan" not in blk:
                self.err("RPL006", w, "block entries must be objects with "
                                      "'rows' and 'plan'")
                return
            rows = blk["rows"]
            if (not isinstance(rows, list) or len(rows) != 2
                    or not all(_is_int(r) for r in rows)):
                self.err("RPL006", f"{w}.rows",
                         f"rows must be an [start, end) integer pair; "
                         f"got {rows!r}")
                return
            s, e = rows
            if s != prev_end or e <= s:
                self.err("RPL006", f"{w}.rows",
                         f"blocks must tile rows contiguously from 0; "
                         f"block {i} covers [{s}, {e}) after row "
                         f"{prev_end}")
            prev_end = e
            if not isinstance(blk["plan"], dict):
                self.err("RPL006", f"{w}.plan", "block plan must be an "
                                                "object")
                continue
            sub_fp = self.exec_plan(blk["plan"], f"{w}.plan.",
                                    allow_hybrid=False)
            if sub_fp is None:
                if blk["plan"].get("fingerprint") is None:
                    self.warn("RPL006", f"{w}.plan",
                              "block sub-plan has no fingerprint")
                all_fp = False
                continue
            nnz_sum += sub_fp["nnz"]
            if sub_fp["n"] != e - s:
                self.err("RPL006", f"{w}.plan.fingerprint",
                         f"sub-plan was minted on {sub_fp['n']} rows but "
                         f"its block spans [{s}, {e})")
        if fp is not None:
            if prev_end != fp["n"]:
                self.err("RPL006", f"{where}blocks",
                         f"blocks cover {prev_end} rows but the plan's "
                         f"fingerprint has n={fp['n']}")
            if all_fp and nnz_sum != fp["nnz"]:
                self.err("RPL006", f"{where}blocks",
                         f"block fingerprints sum to nnz={nnz_sum} but "
                         f"the plan's fingerprint has nnz={fp['nnz']}")

    def sharded(self, d: Dict[str, Any], where: str) -> None:
        for k in d:
            if k not in _SHARDED_KEYS:
                self.warn("RPL001", f"{where}{k}", "unknown plan field")
        if d.get("schema_version") != SHARDED_SCHEMA_VERSION:
            self.err("RPL001", f"{where}schema_version",
                     f"unsupported ShardedPlan schema_version="
                     f"{d.get('schema_version')!r}")
        axis = d.get("axis")
        if axis not in ("row", "col"):
            self.err("RPL007", f"{where}axis",
                     f"unknown sharding axis {axis!r}; one of "
                     f"('row', 'col')")
            axis = "row"
        if not isinstance(d.get("strategy"), str):
            self.err("RPL001", f"{where}strategy",
                     "strategy must be a string")
        batch = d.get("batch", 1)
        if not _is_int(batch) or batch < 1:
            self.err("RPL001", f"{where}batch",
                     f"batch={batch!r} must be a positive integer")
        fp = None
        if d.get("fingerprint") is not None:
            fp = self.fingerprint(d["fingerprint"], where)
        shards = d.get("shards")
        if not isinstance(shards, list) or not shards:
            self.err("RPL007", f"{where}shards",
                     "sharded plan has no shards")
            return
        mesh = d.get("mesh_shape", [])
        if isinstance(mesh, list) and mesh:
            if not all(_is_int(m) and m >= 1 for m in mesh):
                self.err("RPL001", f"{where}mesh_shape",
                         f"mesh_shape must be positive integers; got "
                         f"{mesh!r}")
            else:
                prod = 1
                for m in mesh:
                    prod *= m
                if prod != len(shards):
                    self.warn("RPL007", f"{where}mesh_shape",
                              f"mesh_shape {mesh} addresses {prod} "
                              f"devices but the plan has {len(shards)} "
                              f"shards")
        prev_end, nnz_sum, all_fp = 0, 0, True
        for i, sh in enumerate(shards):
            w = f"{where}shards[{i}]"
            if not isinstance(sh, dict) or "rows" not in sh \
                    or "plan" not in sh:
                self.err("RPL007", w, "shard entries must be objects "
                                      "with 'rows' and 'plan'")
                return
            rows = sh["rows"]
            if (not isinstance(rows, list) or len(rows) != 2
                    or not all(_is_int(r) for r in rows)):
                self.err("RPL007", f"{w}.rows",
                         f"rows must be an [start, end) integer pair; "
                         f"got {rows!r}")
                return
            s, e = rows
            if s != prev_end or e <= s:
                self.err("RPL007", f"{w}.rows",
                         f"shards must tile the {axis} axis contiguously "
                         f"from 0; shard {i} covers [{s}, {e}) after "
                         f"{prev_end}")
            prev_end = e
            if not isinstance(sh["plan"], dict):
                self.err("RPL007", f"{w}.plan", "shard plan must be an "
                                                "object")
                continue
            sub_fp = self.exec_plan(sh["plan"], f"{w}.plan.")
            if sub_fp is None:
                all_fp = False
                if sh["plan"].get("fingerprint") is None:
                    self.err("RPL007", f"{w}.plan",
                             "per-shard fingerprint missing — a replayed "
                             "shard cannot verify its slab")
                continue
            nnz_sum += sub_fp["nnz"]
            if axis == "row" and sub_fp["n"] != e - s:
                self.err("RPL007", f"{w}.plan.fingerprint",
                         f"shard plan was minted on {sub_fp['n']} rows "
                         f"but its slab spans [{s}, {e})")
            if axis == "col" and fp is not None \
                    and sub_fp["n"] != fp["n"]:
                self.err("RPL007", f"{w}.plan.fingerprint",
                         f"column shards keep the full row space "
                         f"(n={fp['n']}) but shard {i} has "
                         f"n={sub_fp['n']}")
        if fp is not None:
            if axis == "row" and prev_end != fp["n"]:
                self.err("RPL007", f"{where}shards",
                         f"shard spans cover {prev_end} rows but the "
                         f"plan's fingerprint has n={fp['n']}")
            if all_fp and nnz_sum != fp["nnz"]:
                self.err("RPL007", f"{where}shards",
                         f"shard fingerprints sum to nnz={nnz_sum} but "
                         f"the plan's fingerprint has nnz={fp['nnz']}")

    # -- streaming artifacts (RPL010) ------------------------------------------
    def _int_list(self, v: Any, where: str, what: str,
                  upper: Optional[int] = None) -> Optional[int]:
        """Check a JSON list of non-negative ints (optionally bounded
        above); returns its length, or None when unusable."""
        if not isinstance(v, list):
            self.err("RPL010", where, f"{what} must be a list; got "
                                      f"{type(v).__name__}")
            return None
        for i, x in enumerate(v):
            if not _is_int(x) or x < 0:
                self.err("RPL010", f"{where}[{i}]",
                         f"{what} entries must be non-negative integers; "
                         f"got {x!r}")
                return None
            if upper is not None and x >= upper:
                self.err("RPL010", f"{where}[{i}]",
                         f"{what} index {x} out of range [0, {upper})")
                return None
        return len(v)

    def delta_batch(self, d: Dict[str, Any], where: str) -> None:
        """A serialized :class:`~repro.stream.delta.DeltaBatch`: the
        bounds that make ``apply_delta`` safe, checkable from JSON."""
        known = {"kind", "schema_version", "n_cols", "appends", "updates",
                 "deletes"}
        for k in d:
            if k not in known:
                self.warn("RPL001", f"{where}{k}", "unknown delta field")
        if d.get("schema_version") != DELTA_SCHEMA_VERSION:
            self.err("RPL010", f"{where}schema_version",
                     f"unsupported delta schema_version="
                     f"{d.get('schema_version')!r}; this linter reads "
                     f"version {DELTA_SCHEMA_VERSION}")
        n_cols = d.get("n_cols")
        if not _is_int(n_cols) or n_cols < 1:
            self.err("RPL010", f"{where}n_cols",
                     f"n_cols={n_cols!r} must be a positive integer")
            n_cols = None
        appends = d.get("appends", [])
        if not isinstance(appends, list):
            self.err("RPL010", f"{where}appends",
                     f"appends must be a list; got "
                     f"{type(appends).__name__}")
        else:
            for i, pair in enumerate(appends):
                w = f"{where}appends[{i}]"
                if not isinstance(pair, (list, tuple)) or len(pair) != 2:
                    self.err("RPL010", w, "append entries must be "
                                          "[cols, vals] pairs")
                    continue
                cols, vals = pair
                nc = self._int_list(cols, f"{w}.cols", "append cols",
                                    upper=n_cols)
                if not isinstance(vals, list):
                    self.err("RPL010", f"{w}.vals",
                             f"append vals must be a list; got "
                             f"{type(vals).__name__}")
                elif not all(_is_num(v) for v in vals):
                    self.err("RPL010", f"{w}.vals",
                             "append vals must be numbers")
                elif nc is not None and len(vals) != nc:
                    self.err("RPL010", w,
                             f"append row has {nc} cols but "
                             f"{len(vals)} vals")
        for section, fields in (("updates", ("rows", "cols", "vals")),
                                ("deletes", ("rows", "cols"))):
            sec = d.get(section, {})
            w = f"{where}{section}"
            if not isinstance(sec, dict):
                self.err("RPL010", w, f"{section} must be an object; got "
                                      f"{type(sec).__name__}")
                continue
            lens = {}
            for f in fields:
                v = sec.get(f, [])
                if f == "vals":
                    if not isinstance(v, list) \
                            or not all(_is_num(x) for x in v):
                        self.err("RPL010", f"{w}.{f}",
                                 f"{section}.{f} must be a list of "
                                 f"numbers")
                        continue
                    lens[f] = len(v)
                else:
                    n = self._int_list(v, f"{w}.{f}", f"{section}.{f}",
                                       upper=(n_cols if f == "cols"
                                              else None))
                    if n is not None:
                        lens[f] = n
            if len(set(lens.values())) > 1:
                self.err("RPL010", w,
                         f"{section} coordinate lists disagree on "
                         f"length: { {f: n for f, n in lens.items()} }")

    def stream_plan(self, d: Dict[str, Any], where: str) -> None:
        """A ``stream_plan`` artifact
        (:meth:`~repro.stream.drift.StreamingPlannedMatrix.to_dict`): the
        wrapped ExecutionPlan gets the full RPL001–RPL009 pass, plus the
        drift-policy and sketch ranges the re-plan trigger relies on."""
        known = {"kind", "schema_version", "key", "plan", "sketch",
                 "policy", "counters"}
        for k in d:
            if k not in known:
                self.warn("RPL001", f"{where}{k}", "unknown stream_plan "
                                                   "field")
        if d.get("schema_version") != STREAM_PLAN_SCHEMA_VERSION:
            self.err("RPL010", f"{where}schema_version",
                     f"unsupported stream_plan schema_version="
                     f"{d.get('schema_version')!r}; this linter reads "
                     f"version {STREAM_PLAN_SCHEMA_VERSION}")
        plan = d.get("plan")
        if not isinstance(plan, dict):
            self.err("RPL010", f"{where}plan",
                     "stream_plan must embed its ExecutionPlan object")
        else:
            self.exec_plan(plan, f"{where}plan.")
        sketch = d.get("sketch")
        fp_n = None
        if not isinstance(sketch, dict):
            self.err("RPL010", f"{where}sketch",
                     "stream_plan must embed its drift sketch")
        else:
            for f in ("n", "nnz", "updates"):
                if not _is_int(sketch.get(f)) or sketch[f] < 0:
                    self.err("RPL010", f"{where}sketch.{f}",
                             f"sketch.{f} must be a non-negative "
                             f"integer; got {sketch.get(f)!r}")
            if not _is_num(sketch.get("sum_sq")) \
                    or sketch["sum_sq"] < 0:
                self.err("RPL010", f"{where}sketch.sum_sq",
                         f"sketch.sum_sq must be a non-negative number; "
                         f"got {sketch.get('sum_sq')!r}")
            hist_n = self._int_list(sketch.get("hist", []),
                                    f"{where}sketch.hist", "sketch.hist")
            if hist_n is not None and _is_int(sketch.get("n")):
                total = sum(sketch["hist"])
                if total != sketch["n"]:
                    self.err("RPL010", f"{where}sketch.hist",
                             f"row-length histogram sums to {total} but "
                             f"the sketch tracks n={sketch['n']} rows")
                fp_n = sketch["n"]
        if isinstance(plan, dict) and fp_n is not None:
            pf = plan.get("fingerprint")
            if isinstance(pf, dict) and _is_int(pf.get("n")) \
                    and pf["n"] != fp_n:
                self.warn("RPL010", f"{where}sketch",
                          f"sketch tracks n={fp_n} rows but the embedded "
                          f"plan was minted on n={pf['n']} — deltas have "
                          f"outgrown the plan (expected between re-plans)")
        policy = d.get("policy")
        if isinstance(policy, dict):
            hyst = policy.get("hysteresis")
            if not _is_num(hyst) or not (0.0 <= hyst < 1.0):
                self.err("RPL010", f"{where}policy.hysteresis",
                         f"hysteresis={hyst!r} must be a number in "
                         f"[0, 1) — at 1 the dead-band swallows the "
                         f"whole boundary")
            for f in ("retransform_factor", "k_hat"):
                v = policy.get(f)
                if v is not None and (not _is_num(v) or v < 0):
                    self.err("RPL010", f"{where}policy.{f}",
                             f"{f}={v!r} must be a non-negative number")
            b = policy.get("batch")
            if b is not None and (not _is_int(b) or b < 1):
                self.err("RPL010", f"{where}policy.batch",
                         f"batch={b!r} must be a positive integer")
            mdb = policy.get("min_deltas_between")
            if mdb is not None and (not _is_int(mdb) or mdb < 0):
                self.err("RPL010", f"{where}policy.min_deltas_between",
                         f"min_deltas_between={mdb!r} must be a "
                         f"non-negative integer")
        elif policy is not None:
            self.err("RPL010", f"{where}policy",
                     f"policy must be an object; got "
                     f"{type(policy).__name__}")
        counters = d.get("counters")
        if isinstance(counters, dict):
            for f, v in counters.items():
                if not _is_int(v) or v < 0:
                    self.err("RPL010", f"{where}counters.{f}",
                             f"counter {f}={v!r} must be a non-negative "
                             f"integer")


def _params_of(d: Dict[str, Any]) -> Dict[str, Any]:
    t = d.get("transform")
    if isinstance(t, dict) and isinstance(t.get("params"), dict):
        return t["params"]
    return {}


def _footprint(gd: Dict[str, Any], fmt: str, op: str,
               params: Dict[str, Any], batch: int) -> Optional[int]:
    """Geometry-driven VMEM working set in bytes, per launch step.

    Counts the buffers whose size the TileGeometry knobs choose — value /
    index slab tiles, segment-pointer windows, and the output tile.  The
    pinned operand ``x`` is excluded: its residency is matrix-shaped
    (``n_cols``), which the plan does not record, and no knob can shrink
    it.  f32 values and i32 indices, 4 bytes each."""
    def knob(name: str, default: int) -> Optional[int]:
        v = gd.get(name, default)
        return v if _is_int(v) and v >= 1 else None

    k = 1
    if op == "spmm":
        bk = knob("block_k", min(_DEFAULT_BK, _align8(max(batch, 1))))
        if bk is None:
            return None
        k = bk
    k8 = _align8(k)                 # sublane-padded right-hand-side rows
    if fmt in ("ell_row", "ell_col", "sell"):
        br, bw = knob("block_rows", _DEFAULT_BR), knob("block_w", _DEFAULT_BW)
        if br is None or bw is None:
            return None
        # double-buffered VAL (bw, br), gathered-x panel (bw, k, br) and
        # output (k, br) tiles
        size = 2 * 4 * (bw * br + bw * k * br + k8 * br)
        buckets = gd.get("buckets")
        if fmt == "sell" and isinstance(buckets, list):
            for pair in buckets:
                if (isinstance(pair, (list, tuple)) and len(pair) == 2
                        and isinstance(pair[1], dict)):
                    sub = _footprint(pair[1], "ell_row", op, params, batch)
                    if sub is not None:
                        size = max(size, sub)
        return size
    if fmt == "csr":
        br = knob("block_rows", _DEFAULT_BR)
        bn = knob("block_nnz", _DEFAULT_BN)
        if br is None or bn is None:
            return None
        # double-buffered row-pointer rows, VAL slab, gathered-x panel and
        # output tiles, plus the (bn, br) one-hot and its compare mask
        return 2 * 4 * (16 * br + 8 * bn + k8 * bn + k8 * br) \
            + 2 * 4 * bn * br
    return None


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def lint_plan(payload: Any,
              vmem_budget: Optional[int] = None) -> List[Finding]:
    """Lint a plan payload dict — ExecutionPlan, ShardedPlan, or a
    streaming artifact (``delta_batch`` / ``stream_plan``), routed on
    ``kind``.  Returns findings; empty means clean.  ``vmem_budget``
    defaults to :data:`DEFAULT_VMEM_BUDGET`."""
    lint = _Lint(vmem_budget if vmem_budget is not None
                 else DEFAULT_VMEM_BUDGET)
    if not isinstance(payload, dict):
        lint.err("RPL001", "plan", f"plan payload must be a JSON object; "
                                   f"got {type(payload).__name__}")
        return lint.findings
    kind = payload.get("kind")
    if kind == "sharded_plan":
        lint.sharded(payload, "")
    elif kind == "delta_batch":
        lint.delta_batch(payload, "")
    elif kind == "stream_plan":
        lint.stream_plan(payload, "")
    else:
        lint.exec_plan(payload, "")
    return lint.findings


def lint_envelope(env: Any,
                  vmem_budget: Optional[int] = None) -> List[Finding]:
    """Lint a :class:`~repro.core.plan_store.PlanStore` envelope
    (``{store_version, sha256, plan}``) — checksum verified here with the
    same canonical-JSON convention the store writes, then the payload is
    linted."""
    if (not isinstance(env, dict) or "plan" not in env
            or "sha256" not in env):
        return [Finding("RPL001", ERROR, "not a plan-store envelope "
                        "(missing 'plan'/'sha256')", where="envelope")]
    findings: List[Finding] = []
    if env.get("store_version") != 1:
        findings.append(Finding(
            "RPL001", ERROR, f"unsupported store_version="
            f"{env.get('store_version')!r}", where="envelope"))
    canonical = json.dumps(env["plan"], sort_keys=True,
                           separators=(",", ":"))
    digest = hashlib.sha256(canonical.encode("utf-8")).hexdigest()
    if digest != env["sha256"]:
        findings.append(Finding(
            "RPL001", ERROR, "envelope sha256 does not match the payload "
            "(bit rot or a tampered entry)", where="envelope"))
    findings.extend(lint_plan(env["plan"], vmem_budget=vmem_budget))
    return findings


def lint_text(text: str,
              vmem_budget: Optional[int] = None) -> List[Finding]:
    """Lint raw JSON text: auto-detects bare plan payloads vs store
    envelopes."""
    try:
        obj = json.loads(text)
    except json.JSONDecodeError as e:
        return [Finding("RPL001", ERROR, f"not valid JSON: {e}")]
    if isinstance(obj, dict) and "sha256" in obj and "plan" in obj:
        return lint_envelope(obj, vmem_budget=vmem_budget)
    return lint_plan(obj, vmem_budget=vmem_budget)


__all__ = ["DEFAULT_VMEM_BUDGET", "KNOWN_FORMATS",
           "KNOWN_OPS", "KNOWN_TIERS", "GEOM_KNOBS",
           "lint_plan", "lint_envelope",
           "lint_text"]
