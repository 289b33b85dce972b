"""Static-analysis subsystem tests (DESIGN.md §15).

Four families:

  * primitives — the contract text checks against synthetic StableHLO.
  * lowering contracts — the §14 zero-overhead guard on the contract
    API, overlap_buckets 1-vs-K donation invariance, partition on/off
    replication pins (4-device host mesh via conftest).
  * kernel budget — the VMEM model vs the real BlockSpec layouts, the
    NS envelope, grid alignment, oversized-tile detection.
  * mutation self-tests — every auditor must FIRE on its seeded
    violation (an auditor that cannot fail is decoration): promote_f64
    -> no_dtype, drop_replication_pin -> replicated, oversized block ->
    budget, synthetic host-sync source -> lint.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from helpers import mesh_of, tiny_cfg, tiny_pipe
from repro.analysis import contracts, dtypes, kernel_budget, lint, mutations
from repro.analysis import runner
from repro.core.optim import make_optimizer
from repro.errors import ConfigError, FormatError
from repro.train import loop as L


# ------------------------------------------------------------- primitives
def test_donation_markers_counts_both_kinds():
    text = ("func @main(%arg0: tensor<4xf32> {tf.aliasing_output = 0 : i32},"
            " %arg1: tensor<4xf32> {jax.buffer_donor = true},"
            " %arg2: tensor<4xf32> {tf.aliasing_output = 1 : i32})")
    m = contracts.donation_markers(text)
    assert m == {"aliased": 2, "donors": 1}
    ok, detail = contracts.check_donates(text, min_aliases=3)
    assert ok, detail
    ok, _ = contracts.check_donates(text, min_aliases=4)
    assert not ok


def test_no_dtype_finds_f64_not_f16():
    good = "stablehlo.add %0, %1 : tensor<8x16xf32>"
    bad = good + "\n  %2 = stablehlo.convert %0 : tensor<8xf64>"
    assert contracts.check_no_dtype(good, "f64")[0]
    ok, detail = contracts.check_no_dtype(bad, "f64")
    assert not ok and "f64" in detail
    # f16 in a shape must not trip the f64 scan ("f64" not a substring)
    assert contracts.check_no_dtype(
        "stablehlo.add %0, %1 : tensor<16xf16>", "f64")[0]


def test_accumulation_sites_and_check():
    text = "\n".join([
        "  %3 = stablehlo.dot_general %1, %2, contracting_dims = [1] x [0]"
        " : (tensor<8x16xf32>, tensor<16x8xf32>) -> tensor<8x8xf32>",
        "  %4 = stablehlo.reduce(%3 init: %c) applies stablehlo.add"
        " across dimensions = [1] : (tensor<8x8xf32>, tensor<f32>)"
        " -> tensor<8xf32>",
        "  %5 = stablehlo.reduce(%i init: %z) applies stablehlo.add"
        " across dimensions = [0] : (tensor<4xi32>, tensor<i32>)"
        " -> tensor<i32>",
    ])
    sites = contracts.accumulation_sites(text)
    assert [op for op, _, _ in sites] == ["dot_general", "reduce_add",
                                          "reduce_add"]
    ok, detail = contracts.check_accumulates_in(text, "f32")
    assert ok, detail          # the i32 reduction is exempt
    bf = text.replace("tensor<8x8xf32>", "tensor<8x8xbf16>")
    ok, detail = contracts.check_accumulates_in(bf, "f32")
    assert not ok and "bf16" in detail


def test_collective_order_checks_first_occurrence():
    text = "aaa SCATTER bbb UPDATE ccc GATHER ddd"
    ok, _ = contracts.check_collective_order(text, "SCATTER", "UPDATE",
                                             "GATHER")
    assert ok
    ok, detail = contracts.check_collective_order(text, "GATHER", "SCATTER")
    assert not ok and "VIOLATED" in detail
    # missing markers: ok only when not required
    ok, _ = contracts.check_collective_order(text, "SCATTER", "MISSING")
    assert not ok
    ok, _ = contracts.check_collective_order(text, "SCATTER", "MISSING",
                                             require_all=False)
    assert ok


def test_lowering_invariant_modes():
    a = "line1\nline2\nline3"
    ok, _ = contracts.lowering_invariant({0: a, 2: a})
    assert ok
    ok, detail = contracts.lowering_invariant({0: a, 2: a.replace("2", "X")})
    assert not ok and "line 2" in detail
    don = "{tf.aliasing_output = 0 : i32}"
    ok, _ = contracts.lowering_invariant(
        {1: "x" + don, 4: "yyy" + don}, compare_aliases_only=True)
    assert ok
    ok, _ = contracts.lowering_invariant(
        {1: don, 4: don * 2}, compare_aliases_only=True)
    assert not ok
    with pytest.raises(contracts.AnalysisError):
        contracts.lowering_invariant({1: a})


def test_registry_register_evaluate_not_applicable():
    contracts.register("tmp.test_contract", "step",
                       lambda low, cell: None if cell is None
                       else (True, "ok"), doc="test")
    try:
        spec = dict((s.name, s) for s in contracts.contracts_for("step"))[
            "tmp.test_contract"]
        low = contracts.Lowering("x", "")
        assert contracts.evaluate(spec, low, None) is None
        r = contracts.evaluate(spec, low, runner.Cell("c", "adamw8", (8, 8)))
        assert r.ok and r.target == "c"
    finally:
        contracts._REGISTRY.pop("tmp.test_contract", None)


# ----------------------------------------------------------- dtype table
def test_dtype_tables_are_shared_and_complete():
    from repro.roofline import analysis as roof
    from repro.roofline import hlo_cost
    assert hlo_cost._DTYPE_BYTES is dtypes.DTYPE_BYTES
    assert roof._DTYPE_BYTES is dtypes.DTYPE_BYTES
    # s4 rounds UP to 1 byte on purpose (HBM buffer storage; see module doc)
    for name, expect in (("f32", 4), ("bf16", 2), ("s4", 1), ("u8", 1),
                         ("f8e4m3fn", 1), ("c128", 16), ("pred", 1)):
        assert dtypes.dtype_bytes(name) == expect
    with pytest.raises(KeyError):
        dtypes.dtype_bytes("f128")


# ------------------------------------------------------ typed exceptions
def test_config_validation_raises_typed_errors():
    with pytest.raises(ConfigError):
        make_optimizer("adamw8", lr=1e-3, overlap_buckets=0)
    with pytest.raises(ConfigError):
        make_optimizer("adamw8", lr=1e-3, state_bits=3)
    with pytest.raises(FormatError):
        from repro.core.lowbit import packed_width
        packed_width(3, 4)  # 12 bits don't fill whole bytes
    # ConfigError/FormatError stay ValueError for existing except-clauses
    assert issubclass(ConfigError, ValueError)
    assert issubclass(FormatError, ValueError)


# ------------------------------------------------- lowering contracts
def _pooled_step_text(**overrides):
    cfg = tiny_cfg()
    pipe = tiny_pipe(vocab_size=cfg.vocab_size)
    batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(0).items()}
    opt = make_optimizer("adam8", lr=5e-3, min_8bit_size=1024, **overrides)
    state, _ = L.init_train_state(cfg, opt, jax.random.PRNGKey(0))
    return L.jit_train_step(cfg, opt).lower(state, batch).as_text()


def test_telemetry_guard_on_contract_api():
    """The §14 zero-overhead guard via lowering_invariant (ex-PR-7 test)."""
    texts = {n: _pooled_step_text(telemetry_every=n) for n in (0, 2)}
    ok, detail = contracts.lowering_invariant(texts)
    assert ok, detail
    assert "tel." not in texts[0]


def test_overlap_buckets_donation_invariant():
    """overlap_buckets 1 vs K restructures dispatch but must keep every
    donated in-place arena (§13c) — the pair:overlap contract."""
    mesh = mesh_of(4)
    texts = {}
    for k in (1, 2):
        cell = runner.Cell(f"ov{k}", "adamw8", (8, 8), partition=4,
                           shard_grads=True, overlap_buckets=k)
        texts[k] = runner.lower_step(cell).text
    ok, detail = contracts.lowering_invariant(texts,
                                              compare_aliases_only=True)
    assert ok, detail
    del mesh


def test_partition_toggles_replication_pins():
    """partition on -> §12 replication pins appear; off -> none
    (the pair:partition contract)."""
    mesh = mesh_of(4)
    on = runner.lower_step(runner.Cell("on", "adamw8", (8, 8), partition=4))
    off = runner.lower_step(runner.Cell("off", "adamw8", (8, 8)))
    pins_on = contracts.replicated_pins(on.text)
    pins_off = contracts.replicated_pins(off.text)
    assert pins_on >= 1 and pins_off == 0, (pins_on, pins_off)
    ok, detail = contracts.check_replicated(on.text)
    assert ok, detail
    del mesh


def test_runner_matrix_cell_passes_all_step_contracts():
    """One full matrix cell end-to-end through the registered contracts."""
    import repro.kernels.ops  # noqa: F401 — registration side effects
    import repro.sharding.rules  # noqa: F401
    import repro.train.loop  # noqa: F401
    mesh_of(4)
    cell = runner.Cell("zero2", "adamw8", (8, 8), partition=4,
                       shard_grads=True, overlap_buckets=2)
    low = runner.lower_step(cell)
    assert low is not None
    results = [contracts.evaluate(s, low, cell)
               for s in contracts.contracts_for("step")]
    results = [r for r in results if r is not None]
    assert results and all(r.ok for r in results), \
        [str(r) for r in results if not r.ok]


# ------------------------------------------------------- kernel budget
def test_fused_update_tile_matches_blockspec_layout():
    """The VMEM mirror must agree with the real in_specs assembly: the
    streamed input bytes of one adamw tile are exactly the BlockSpec
    shapes of fused_update_pallas (p, g, codes_m, absmax_m, codes_r,
    absmax_r) and the outputs mirror them."""
    rows, bsz = 8, 2048
    t = kernel_budget.fused_update_tile("adamw", rows=rows, block_size=bsz)
    row = rows * bsz * 4
    assert t.streamed_in == 2 * row + rows * bsz + rows * 4 \
        + rows * bsz + rows * 4          # p,g + cm,am + cr,ar
    assert t.streamed_out == row + rows * bsz + rows * 4 \
        + rows * bsz + rows * 4
    # 4-bit momentum halves the state-1 code stream exactly
    t4 = kernel_budget.fused_update_tile("adamw", rows=rows, block_size=bsz,
                                         bits_m=4)
    assert t.streamed_in - t4.streamed_in == rows * bsz // 2
    # lars adds the tensor-scale slice, single state
    tl = kernel_budget.fused_update_tile("lars", rows=rows, block_size=bsz)
    assert tl.config["bits_r"] is None


def test_budget_counts_codebook_lane_tables():
    """Codebooks and boundaries are (2, 128) f32 VMEM blocks (the kernels'
    ``common.table_spec``), each filling one (8, 128) f32 tile: adamw holds
    four (two per state), lars two, quantize one (boundaries) and
    dequantize one (codebook); the scalars vector stays in SMEM."""
    from repro.kernels import common as kernels_common
    from repro.kernels import fused_update

    assert kernels_common.table_spec().block_shape == (2, 128)
    table = kernel_budget.TABLE_BYTES
    assert table == 8 * 128 * 4
    scal = fused_update.N_SCALARS * 4
    assert kernel_budget.fused_update_tile("adamw").invariant == \
        scal + 4 * table
    assert kernel_budget.fused_update_tile("lars").invariant == \
        scal + 2 * table
    assert kernel_budget.quantize_tile().invariant == table
    assert kernel_budget.dequantize_tile().invariant == table


def test_budget_audit_clean_and_oversized_detected():
    results = kernel_budget.audit()
    bad = [r for r in results if not r[1]]
    assert not bad, bad
    # mutation: an absurd block size must blow the budget
    big = kernel_budget.fused_update_tile("adamw", block_size=1 << 19)
    assert not big.fits()
    assert big.headroom() < 0


def test_ns_envelope_and_matrix_rejected():
    assert kernel_budget.ns_max_m() >= 1024
    with pytest.raises(contracts.AnalysisError):
        kernel_budget.fused_update_tile("muon")


def test_grid_alignment_checks():
    from repro.core.optim import base as optim_base

    ok, detail = kernel_budget.check_grid_alignment(12345, 4, 2, grid=8)
    assert ok, detail
    # production grid: shard_multiple == mesh size, distinct from kernel rows
    ok, detail = kernel_budget.check_grid_alignment(1000, 4, 2, grid=4)
    assert ok, detail

    # The checker must actually be able to fail: corrupt a valid plan and
    # assert each corruption class fires.
    part = optim_base.make_partition(1000, 4, grid=4)
    plan = optim_base.make_buckets(part, 2, grid=4)
    ok, _ = kernel_budget.check_partition_plan(part, plan, grid=4)
    assert ok

    # misaligned bucket boundary inside the span
    bad_ranges = ((0, 3),) + tuple((3 if k0 == plan.ranges[1][0] else k0, k1)
                                   for k0, k1 in plan.ranges[1:])
    bad_plan = dataclasses.replace(plan, ranges=bad_ranges)
    ok, detail = kernel_budget.check_partition_plan(part, bad_plan, grid=4)
    assert not ok and "misaligned" in detail

    # non-contiguous / non-covering bucket ranges
    gap_plan = dataclasses.replace(plan, ranges=plan.ranges[:-1])
    ok, detail = kernel_budget.check_partition_plan(part, gap_plan, grid=4)
    assert not ok

    # span_pad off the grid
    bad_part = dataclasses.replace(part, span_pad=part.span_pad + 1)
    ok, detail = kernel_budget.check_partition_plan(bad_part, None, grid=4)
    assert not ok and "span_pad" in detail


def test_budget_table_shape():
    table = kernel_budget.budget_table()
    kernels = {row["kernel"] for row in table}
    assert {"fused_update", "blockwise_quant", "blockwise_dequant",
            "newton_schulz_gram", "newton_schulz_apply"} <= kernels
    for row in table:
        assert row["total_bytes"] == (
            2 * (row["streamed_in_bytes"] + row["streamed_out_bytes"])
            + row["invariant_bytes"] + row["scratch_bytes"])


# ----------------------------------------------------- mutation self-tests
def test_mutation_promote_f64_trips_no_dtype():
    """Seeded f64 promotion in ops.fused_update must trip no_dtype(f64).
    x64 mode is enabled only around the bare update lowering — without it
    the astype silently stays f32 and the mutation proves nothing."""
    # Clean reference lowered in normal (x64-off) mode: under enable_x64
    # even an unmutated lowering carries f64 weak-typed constants, so the
    # clean check must use the production trace mode.
    clean = runner.lower_update("adamw", 8)
    assert contracts.check_no_dtype(clean.text, "f64")[0] is True
    with jax.enable_x64(True):
        with mutations.seeded("promote_f64"):
            mutated = runner.lower_update("adamw", 8)
    ok, detail = contracts.check_no_dtype(mutated.text, "f64")
    assert not ok, "auditor failed to fire on seeded f64 promotion"
    assert "f64" in detail


def test_mutation_drop_replication_pin_trips_replicated():
    """Dropping replicate_for_scales must strip the §12 scale pins and trip
    the registered replicated_scales auditor.  The arena layout pins the
    (256,) codebook constants and a few scalars independently, so the
    auditor counts vector pins excluding the codebook shape — those must
    go to exactly zero under the mutation."""
    from repro.kernels import common as kernels_common
    from repro.sharding import rules  # ensure auditor registration

    mesh_of(4)
    cell = runner.Cell("mut", "adamw8", (8, 8), partition=4)
    codebook = ((kernels_common.CODEBOOK_SIZE,),)
    clean = runner.lower_step(cell)
    assert contracts.check_replicated(clean.text, vectors_only=True,
                                      exclude_shapes=codebook)[0]
    with mutations.seeded("drop_replication_pin"):
        mutated = runner.lower_step(cell)
    pins = contracts.replicated_pins(mutated.text, vectors_only=True,
                                     exclude_shapes=codebook)
    assert pins == 0, f"mutation left {pins} scale pins"
    # the registered auditor itself must fire on the mutated lowering
    (contract,) = [c for c in contracts.all_contracts()
                   if c.name == "partitioned_step.replicated_scales"]
    ok, detail = contract.check(mutated, cell)
    assert not ok, f"auditor failed to fire: {detail}"


def test_mutation_unknown_name_rejected():
    with pytest.raises(ValueError):
        with mutations.seeded("not_a_mutation"):
            pass
    assert not mutations.active("promote_f64")


def test_mutation_host_sync_lint_fires(tmp_path):
    """The host-sync rule must fire on a jitted function calling .item()
    (static lint runs on source, so the violation is a synthetic file)."""
    bad = tmp_path / "bad_module.py"
    bad.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    s = x.sum().item()\n"
        "    y = jax.device_get(x)\n"
        "    return s, y\n")
    vs = lint.lint_paths(str(tmp_path))
    rules = sorted(v.rule for v in vs)
    assert rules == ["host-sync-in-jit", "host-sync-in-jit"], vs


def test_lint_rules_on_synthetic_sources(tmp_path):
    (tmp_path / "m.py").write_text(
        "import os\n"
        "import os\n"
        "def f():\n"
        "    assert True\n"
        "    return os.environ.get('X')\n")
    vs = lint.lint_paths(str(tmp_path))
    rules = sorted(v.rule for v in vs)
    assert rules == ["bare-assert", "duplicate-import", "env-read-at-trace"]


def test_lint_baseline_gate(tmp_path):
    (tmp_path / "m.py").write_text("def f():\n    assert True\n")
    base = tmp_path / "baseline.json"
    ok, _ = lint.run(str(tmp_path), baseline_path=str(base))
    assert not ok                               # no baseline: new violation
    ok, _ = lint.run(str(tmp_path), baseline_path=str(base),
                     update_baseline=True)
    assert ok and json.loads(base.read_text()) == {"m.py::bare-assert": 1}
    ok, _ = lint.run(str(tmp_path), baseline_path=str(base))
    assert ok                                   # baselined
    (tmp_path / "m.py").write_text(
        "def f():\n    assert True\n    assert False\n")
    ok, lines = lint.run(str(tmp_path), baseline_path=str(base))
    assert not ok and any("NEW" in ln for ln in lines)


def test_repo_lint_is_clean_against_baseline():
    import os
    # repro is a namespace package (__file__ is None); anchor on a module
    root = os.path.dirname(os.path.dirname(lint.__file__))
    ok, lines = lint.run(root)
    assert ok, "\n".join(lines)
