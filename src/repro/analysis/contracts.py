"""Compile-contract checker: declarative invariants over lowered StableHLO.

The repo's correctness story rests on properties of the *compiled* step,
not of any particular run: the TrainState is donated in place (§13c), no
update math silently promotes dtype (§6), LAMB/LARS/NS accumulation stays
f32, the §12 replication pins survive partitioning, and host-side knobs
(``telemetry_every``) never change the lowering.  PR 7 checked two of
these with one-off tests; this module generalizes them into contracts —
small named checks over ``jax.jit(...).lower(...).as_text()`` — that are
**registered next to the code they protect** (train/loop.py,
kernels/ops.py, sharding/rules.py call :func:`register` at import) and
evaluated over a config matrix by ``python -m repro.analysis`` without
executing a single training step.

This module is deliberately import-light (stdlib only): production
modules import it at module level to register their contracts, so it must
never pull in jax or the subsystems it audits.  The heavy lowering
construction lives in :mod:`repro.analysis.runner`.

Scopes bind a contract to the lowering(s) it runs on:

  * ``"step"``    — every lowered train step in the config matrix.
  * ``"update"``  — the bare fused-update lowering per (algo, bits).
  * ``"pair:telemetry"`` / ``"pair:overlap"`` / ``"pair:partition"`` —
    two lowerings differing only in one knob (``telemetry_every`` 0 vs N,
    ``overlap_buckets`` 1 vs K, ``partition_shards`` 1 vs N).

Checks take ``(lowering, cell)`` — or ``(dict_of_lowerings, cell)`` for
pair scopes — and return a ``(ok, detail)`` tuple or ``None`` for
"not applicable to this cell".
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Optional


class AnalysisError(Exception):
    """A static-analysis contract or budget violation."""


@dataclasses.dataclass(frozen=True)
class Lowering:
    """One ``.lower()``-ed computation: its name and StableHLO text."""
    name: str
    text: str


@dataclasses.dataclass(frozen=True)
class ContractResult:
    contract: str
    target: str
    ok: bool
    detail: str = ""

    def __str__(self):
        mark = "PASS" if self.ok else "FAIL"
        d = f" — {self.detail}" if self.detail else ""
        return f"[{mark}] {self.contract} @ {self.target}{d}"


# ------------------------------------------------------------ text checks
# StableHLO shape strings: tensor<8x128xf32>, tensor<f32>, tensor<4xui8>.
_ELEM_RE = re.compile(r"tensor<(?:[0-9]+x)*([a-z][a-z0-9]*)>")
_RESULT_TYPE_RE = re.compile(r"->\s*(\(?tensor<[^)]*?>\)?)\s*$")


def donation_aliases(text: str) -> int:
    """Number of donated-input/output buffer aliasings the lowering
    established — the ``tf.aliasing_output`` markers in the StableHLO
    (the §13c audit, generalized from train/loop.py)."""
    return text.count("tf.aliasing_output")


def donation_markers(text: str) -> dict:
    """Both donation marker kinds in a lowering: ``aliased`` inputs whose
    output aliasing was already established at lowering time
    (``tf.aliasing_output``), and ``donors`` deferred to the compiler
    (``jax.buffer_donor`` — what XLA emits when input shardings are
    unresolved at lowering, e.g. the partitioned/shard_map step)."""
    return {"aliased": donation_aliases(text),
            "donors": text.count("jax.buffer_donor")}


def check_donates(text: str, min_aliases: int = 1) -> tuple:
    """``donates(TrainState)``: the step must mark at least
    ``min_aliases`` donated inputs (established aliasings or deferred
    buffer donors) — a donated state that establishes zero of either
    round-trips every arena through HBM twice."""
    m = donation_markers(text)
    n = m["aliased"] + m["donors"]
    ok = n >= min_aliases
    return ok, (f"{m['aliased']} aliasing(s) + {m['donors']} donor "
                f"mark(s), need >= {min_aliases}")


def find_dtype(text: str, dtype: str) -> list:
    """Lines mentioning HLO dtype ``dtype`` (as a shape element type)."""
    pat = re.compile(rf"(?:<|x){re.escape(dtype)}(?:>|\b)")
    return [ln.strip() for ln in text.splitlines() if pat.search(ln)]


def check_no_dtype(text: str, dtype: str = "f64") -> tuple:
    """``no_dtype(f64)``: the lowering must not contain the banned dtype
    anywhere — one stray promotion breaks the §6 master-dtype policy (and
    on TPU silently deoptimizes instead of failing)."""
    hits = find_dtype(text, dtype)
    ok = not hits
    detail = f"no {dtype} anywhere" if ok else \
        f"{len(hits)} {dtype} site(s), e.g.: {hits[0][:120]}"
    return ok, detail


def accumulation_sites(text: str) -> list:
    """(op, elem_dtype, line) for every accumulation-class op in the text:
    ``stablehlo.dot_general``/``stablehlo.dot`` and additive
    ``stablehlo.reduce`` forms with a result type on the same line."""
    out = []
    for ln in text.splitlines():
        s = ln.strip()
        op = None
        if "stablehlo.dot_general" in s or "stablehlo.dot " in s:
            op = "dot_general"
        elif "stablehlo.reduce" in s and "applies stablehlo.add" in s:
            op = "reduce_add"
        if op is None:
            continue
        m = _RESULT_TYPE_RE.search(s)
        if not m:
            continue
        elems = _ELEM_RE.findall(m.group(1))
        for e in elems:
            out.append((op, e, s))
    return out


def check_accumulates_in(text: str, dtype: str = "f32",
                         allow: tuple = ("i32", "i64", "ui32",
                                         "i8", "ui8", "i1")) -> tuple:
    """``accumulates_in(f32)``: every matmul / additive reduction in the
    lowering lands in ``dtype`` (integer reductions are exempt) — the
    precision-fragility guard for the fused-update and Newton–Schulz math
    (Li et al. 2023; SOLO): a bf16 gram accumulation would pass every
    shape check and quietly widen the quantization error band."""
    sites = accumulation_sites(text)
    bad = [(op, e, ln) for op, e, ln in sites
           if e != dtype and e not in allow]
    ok = not bad
    detail = f"{len(sites)} accumulation site(s), all {dtype}/integer" \
        if ok else (f"{len(bad)} site(s) accumulate outside {dtype}, "
                    f"e.g. {bad[0][1]} in: {bad[0][2][:110]}")
    return ok, detail


# Shardy, the partitioner jax 0.9 lowers to: a replicated pin is
# ``sdy.sharding_constraint %x <@mesh, [{}, {}]> : tensor<...>`` — every
# dimension's axis list empty.
_SDY_PIN_RE = re.compile(
    r"sdy\.sharding_constraint %\S+ <@\w+, \[([^\]]*)\]>"
    r"(?: : tensor<(?:(\d+(?:x\d+)*)x)?[a-z0-9]+>)?")

# The SPMD boundaries of a shard_map region in Shardy text, in program
# order: a sharded pin (grads into the owned-span layout), the manual
# region's entry (reduce-scatter side) and its exit (all-gather side).
SPMD_BOUNDARY_MARKERS = ("sdy.sharding_constraint", "sdy.manual_computation",
                         "sdy.return")


def replicated_pins(text: str, vectors_only: bool = False,
                    exclude_shapes: tuple = ()) -> int:
    """Number of fully-replicated sharding pins in the lowering — the
    ``sdy.sharding_constraint`` sites with every dimension unsharded that
    ``rules.replicate_for_scales`` emits (DESIGN.md §12).

    ``vectors_only`` skips scalar pins and ``exclude_shapes`` skips named
    operand shapes (e.g. the ``(256,)`` codebook constants, which are
    pinned by the arena layout, not by replicate_for_scales) — so callers
    can count specifically the per-tensor scale pins."""
    n = 0
    for m in _SDY_PIN_RE.finditer(text):
        if m.group(1).replace("{}", "").strip(", "):
            continue                        # some dimension is sharded
        dims = tuple(int(d) for d in m.group(2).split("x")) \
            if m.group(2) else ()
        if vectors_only and not dims:
            continue
        if dims in tuple(exclude_shapes):
            continue
        n += 1
    return n


def check_replicated(text: str, min_pins: int = 1, *,
                     vectors_only: bool = False,
                     exclude_shapes: tuple = ()) -> tuple:
    """``replicated(tensor_scales, gnorm_vec)``: a partitioned lowering
    must pin its global-scale reductions fully replicated (§12) — without
    the pin SPMD may distribute the reduction and change the f32 summation
    order, silently breaking the partitioned/unpartitioned bit-exactness
    contract."""
    n = replicated_pins(text, vectors_only=vectors_only,
                        exclude_shapes=exclude_shapes)
    ok = n >= min_pins
    return ok, f"{n} replicated pin(s), need >= {min_pins}"


def marker_positions(text: str, markers) -> list:
    """First-occurrence index of each marker substring (-1 = absent)."""
    return [text.find(m) for m in markers]


def check_collective_order(text: str, *markers, require_all=True) -> tuple:
    """``collective_order(a -> b -> ...)``: the first occurrence of each
    marker must appear in the given order.  Used for the §13 step shape —
    the params all-gather (serving the previous update's deferred
    materialization) precedes the grad reduce-scatters, which precede the
    update's donated writeback."""
    pos = marker_positions(text, markers)
    missing = [m for m, p in zip(markers, pos) if p < 0]
    if missing:
        return (not require_all), f"marker(s) absent: {missing}"
    present = [(m, p) for m, p in zip(markers, pos)]
    ordered = all(p1 < p2 for (_, p1), (_, p2) in zip(present, present[1:]))
    chain = " -> ".join(m for m, _ in present)
    return ordered, f"first-occurrence order {'holds' if ordered else 'VIOLATED'}: {chain}"


def lowering_invariant(texts: dict, *, compare_aliases_only: bool = False
                       ) -> tuple:
    """``lowering_invariant_to(knob)``: the PR-7 zero-overhead guard as a
    reusable API.  ``texts`` maps knob values to StableHLO text; with
    ``compare_aliases_only=False`` all texts must be *byte-identical*
    (the knob is host-schedule only); with ``True`` only the donation-
    aliasing counts must match (the knob may restructure the computation
    — e.g. ``overlap_buckets`` changes bucketing — but must never cost an
    in-place arena)."""
    items = sorted(texts.items(), key=lambda kv: str(kv[0]))
    if len(items) < 2:
        raise AnalysisError("lowering_invariant needs >= 2 lowerings")
    if compare_aliases_only:
        counts = {k: sum(donation_markers(t).values()) for k, t in items}
        vals = set(counts.values())
        ok = len(vals) == 1 and next(iter(vals)) > 0
        return ok, f"donation marks per knob value: {counts}"
    base_k, base_t = items[0]
    for k, t in items[1:]:
        if t != base_t:
            # locate the first differing line for the report
            a, b = base_t.splitlines(), t.splitlines()
            for i, (la, lb) in enumerate(zip(a, b)):
                if la != lb:
                    return False, (f"knob {base_k!r} vs {k!r}: lowering "
                                   f"diverges at line {i + 1}: "
                                   f"{la.strip()[:60]!r} != "
                                   f"{lb.strip()[:60]!r}")
            return False, (f"knob {base_k!r} vs {k!r}: lowering lengths "
                           f"differ ({len(a)} vs {len(b)} lines)")
    return True, f"{len(items)} lowering(s) byte-identical"


# --------------------------------------------------------------- registry
@dataclasses.dataclass(frozen=True)
class ContractSpec:
    """One registered contract: a named check bound to a scope.  ``check``
    takes ``(lowering_or_pair, cell)`` and returns ``(ok, detail)`` or
    ``None`` (not applicable to this cell)."""
    name: str
    scope: str
    check: Callable[[Any, Any], Optional[tuple]]
    doc: str = ""


_REGISTRY: dict = {}


def register(name: str, scope: str, check: Callable, doc: str = "") -> None:
    """Register (or re-register — module reloads are idempotent) a
    contract.  Call this next to the code the contract protects."""
    _REGISTRY[name] = ContractSpec(name=name, scope=scope, check=check,
                                   doc=doc)


def contracts_for(scope: str) -> list:
    """Registered contracts bound to ``scope``, name-ordered."""
    return [s for _, s in sorted(_REGISTRY.items()) if s.scope == scope]


def all_contracts() -> list:
    return [s for _, s in sorted(_REGISTRY.items())]


def evaluate(spec: ContractSpec, subject, cell) -> Optional[ContractResult]:
    """Run one contract; ``None`` means not applicable."""
    out = spec.check(subject, cell)
    if out is None:
        return None
    ok, detail = out
    target = getattr(cell, "name", None) or getattr(subject, "name", "?")
    return ContractResult(contract=spec.name, target=str(target),
                          ok=bool(ok), detail=detail)
