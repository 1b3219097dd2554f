"""The program-level audit (ISSUE 13 tentpole): FTP rules over the
lowered builder cells.

Three layers, mirroring the module's own split:

* **seeded text checks** — each FTP text rule fires on a handcrafted
  StableHLO snippet carrying exactly that violation (and stays quiet
  on the clean twin);
* **seeded lowerings** — real jax programs with an injected violation
  (an f64 cast under x64, a ``jax.debug.print`` host callback, a
  dropped ``donate_argnums``) produce findings through the same
  extraction path the audit uses;
* **the full matrix** — ``audit_programs()`` lowers every legal
  builder cell on the CPU backend and must land ZERO findings with
  the shipped (empty) baseline, refuse the two illegal cells, and
  stay far under the 120 s tier-1 budget.
"""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from fedtorch_tpu.lint.program_audit import (
    AUDIT_SCAN_LENGTH, LARGE_CONST_BYTES, audit_programs,
    check_collectives, check_donation, check_dtype_promotion,
    check_host_transfers, check_large_constants, check_peak_hbm,
    load_program_baseline, lower_cell, save_program_baseline,
)

CELL = "(resident x round x vmap)"


# -- seeded text checks ------------------------------------------------------

CLEAN_HLO = """\
module @jit_round {
  func.func public @main(%arg0: tensor<8x8xf32> {tf.aliasing_output = 0 : i32}) -> tensor<8x8xf32> {
    %0 = stablehlo.add %arg0, %arg0 : tensor<8x8xf32>
    %1 = stablehlo.custom_call @Sharding(%0) : (tensor<8x8xf32>) -> tensor<8x8xf32>
    return %1 : tensor<8x8xf32>
  }
}
"""


class TestSeededText:
    def test_ftp001_f64(self):
        bad = CLEAN_HLO.replace(
            "stablehlo.add %arg0, %arg0 : tensor<8x8xf32>",
            "stablehlo.convert %arg0 : (tensor<8x8xf32>) -> tensor<8x8xf64>")
        fs = check_dtype_promotion(bad, CELL)
        assert [f.rule for f in fs] == ["FTP001"]
        assert check_dtype_promotion(CLEAN_HLO, CELL) == []

    def test_ftp001_f32_dot_in_bf16_program(self):
        dot = ("    %2 = stablehlo.dot_general %0, %0, contracting_dims "
               "= [1] x [0] : (tensor<8x8xf32>, tensor<8x8xf32>) -> "
               "tensor<8x8xf32>\n")
        bad = CLEAN_HLO.replace("    return", dot + "    return")
        assert [f.rule for f in check_dtype_promotion(
            bad, CELL, compute_dtype="bfloat16")] == ["FTP001"]
        # the same program is fine under the f32 contract
        assert check_dtype_promotion(bad, CELL) == []
        # and a bf16 dot is fine under the bf16 contract
        ok = bad.replace("xf32>", "xbf16>")
        assert check_dtype_promotion(ok, CELL,
                                     compute_dtype="bfloat16") == []

    def test_ftp002_outfeed_and_callback(self):
        bad = CLEAN_HLO.replace(
            "    return",
            '    "stablehlo.outfeed"(%0) : (tensor<8x8xf32>) -> ()\n'
            "    return")
        assert [f.rule for f in check_host_transfers(bad, CELL)] \
            == ["FTP002"]
        bad2 = CLEAN_HLO.replace(
            "custom_call @Sharding",
            "custom_call @xla_python_cpu_callback")
        assert [f.rule for f in check_host_transfers(bad2, CELL)] \
            == ["FTP002"]
        assert check_host_transfers(CLEAN_HLO, CELL) == []

    def test_ftp003_dropped_donation(self):
        bad = CLEAN_HLO.replace(" {tf.aliasing_output = 0 : i32}", "")
        fs = check_donation(bad, CELL, donated_leaves=1)
        assert [f.rule for f in fs] == ["FTP003"]
        assert check_donation(CLEAN_HLO, CELL, donated_leaves=1) == []
        assert check_donation(bad, CELL, donated_leaves=0) == []

    def test_ftp004_collectives_over_budget(self):
        two = CLEAN_HLO.replace(
            "    return",
            '    %c1 = "stablehlo.all_reduce"(%0) : (tensor<8x8xf32>) -> tensor<8x8xf32>\n'
            '    %c2 = "stablehlo.all_reduce"(%0) : (tensor<8x8xf32>) -> tensor<8x8xf32>\n'
            "    return")
        assert [f.rule for f in check_collectives(two, CELL, budget=1)] \
            == ["FTP004"]
        assert check_collectives(two, CELL, budget=2) == []
        assert check_collectives(CLEAN_HLO, CELL, budget=0) == []

    def test_ftp005_large_constant(self):
        small = [("float32[8]", 32)]
        big = [("float32[200,200]", 160_000)]
        assert check_large_constants(small, CELL) == []
        fs = check_large_constants(big, CELL)
        assert [f.rule for f in fs] == ["FTP005"]
        assert big[0][1] > LARGE_CONST_BYTES  # seeded above threshold

    def test_ftp006_peak_regression(self):
        assert check_peak_hbm(1000.0, CELL, {}) == []          # unpinned
        assert check_peak_hbm(None, CELL, {CELL: 500.0}) == []  # no stat
        assert check_peak_hbm(510.0, CELL, {CELL: 500.0}) == []  # in tol
        fs = check_peak_hbm(600.0, CELL, {CELL: 500.0})
        assert [f.rule for f in fs] == ["FTP006"]


# -- seeded real lowerings ---------------------------------------------------

class TestSeededLowerings:
    def test_injected_f64_cast_fires(self):
        with jax.enable_x64():
            low = jax.jit(lambda x: x.astype(jnp.float64) * 2).lower(
                jax.ShapeDtypeStruct((4,), jnp.float32))
            text = low.as_text()
        assert [f.rule for f in check_dtype_promotion(text, CELL)] \
            == ["FTP001"]

    def test_debug_print_fires_ftp002(self):
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x + 1
        text = jax.jit(f).lower(
            jax.ShapeDtypeStruct((4,), jnp.float32)).as_text()
        assert "FTP002" in {f.rule for f in
                            check_host_transfers(text, CELL)}

    def test_dropped_donate_argnums_fires_ftp003(self):
        def f(a, b):
            return a + b, b
        s = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        donated = jax.jit(f, donate_argnums=(0,)).lower(s, s).as_text()
        dropped = jax.jit(f).lower(s, s).as_text()
        assert check_donation(donated, CELL, donated_leaves=1) == []
        assert [f.rule for f in
                check_donation(dropped, CELL, donated_leaves=1)] \
            == ["FTP003"]


# -- the full builder-cell matrix -------------------------------------------

class TestFullMatrix:
    def test_every_cell_lowers_clean_with_empty_baseline(self, tmp_path):
        """The acceptance bar: all legal cells lower and pass with an
        empty FTP baseline, the sequential commit cells refuse, and
        the whole audit stays far inside the 120 s tier-1
        budget."""
        t0 = time.time()
        new, report = audit_programs(log=lambda *_: None)
        wall = time.time() - t0
        assert new == [], [f.render() for f in new]
        legal = {c: r for c, r in report["cells"].items() if r["legal"]}
        refused = {c: r for c, r in report["cells"].items()
                   if not r["legal"]}
        # 10 legal cells (4 of them the sequential execution's) + the
        # 6 [shards=2] pod-scale twins of the vmap cells (+ bf16 twins
        # of the vmap round/scan cells)
        assert len([c for c in legal if "[bfloat16]" not in c
                    and "[shards=" not in c]) == 10
        assert len([c for c in legal if "[shards=" in c]) == 6
        assert len([c for c in legal if "[bfloat16]" in c]) == 4
        assert set(refused) == {"(resident x commit x sequential)",
                                "(feed x commit x sequential)"}
        for cell, rec in refused.items():
            assert cell in rec["refusal"]
        assert wall < 120.0, f"audit took {wall:.1f}s"

    def test_cell_evidence_shape(self):
        ev = lower_cell("feed", "scan", "vmap",
                        scan_length=AUDIT_SCAN_LENGTH)
        assert ev["program"].startswith("rounds_stream_scan")
        assert ev["donated_leaves"] > 0
        assert "stablehlo" in ev["text"] or "func.func" in ev["text"]

    def test_baseline_roundtrip_and_ftp006_gate(self, tmp_path):
        path = str(tmp_path / "program_baseline.json")
        save_program_baseline(path, [], {CELL: 500.0})
        fps, peaks = load_program_baseline(path)
        assert not fps and peaks == {CELL: 500.0}
        doc = json.load(open(path))
        assert doc["version"] == 1
        # a grown watermark now fails through the same check the audit
        # runs per cell
        assert [f.rule for f in check_peak_hbm(600.0, CELL, peaks)] \
            == ["FTP006"]

    def test_shipped_baseline_is_empty(self):
        fps, peaks = load_program_baseline()
        assert sum(fps.values()) == 0
        # peaks may be pinned later by a chip capture; fingerprints
        # must stay empty (findings are fixed, not accepted)


def test_audit_cli_routes():
    """`fedtorch-tpu audit --registry-only` runs jax-free and green."""
    from fedtorch_tpu.cli import main
    assert main(["audit", "--registry-only"]) == 0
