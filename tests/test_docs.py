"""Docs consistency: DESIGN.md §-references and README quickstart commands.

Module docstrings across the repo cite architecture sections as
``DESIGN.md §N``; this gate fails when a cited section does not exist, and
when a README command names a module or script that is not in the tree —
so the docs cannot silently rot as the code moves.
"""

import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _py_files():
    for sub in ("src", "tests", "benchmarks", "examples"):
        yield from (ROOT / sub).rglob("*.py")


def _design_sections():
    text = (ROOT / "DESIGN.md").read_text()
    return {int(m) for m in re.findall(r"(?m)^## §(\d+)", text)}


def test_design_section_references_exist():
    sections = _design_sections()
    assert sections, "DESIGN.md has no '## §N' sections"
    missing = []
    for path in _py_files():
        for n in re.findall(r"DESIGN\.md §(\d+)", path.read_text()):
            if int(n) not in sections:
                missing.append((str(path.relative_to(ROOT)), int(n)))
    assert not missing, (
        f"dangling DESIGN.md § references (existing: {sorted(sections)}): "
        f"{missing}")


def test_design_references_from_markdown():
    """README/CHANGES §-citations must resolve too."""
    sections = _design_sections()
    for name in ("README.md", "CHANGES.md"):
        text = (ROOT / name).read_text()
        for n in re.findall(r"DESIGN\.md[^#\n]{0,20}§(\d+)", text):
            assert int(n) in sections, f"{name} cites missing DESIGN.md §{n}"


def test_readme_exists_and_commands_resolve():
    readme = ROOT / "README.md"
    assert readme.exists(), "top-level README.md is required"
    text = readme.read_text()

    # `python -m pkg.mod` → src/pkg/mod.py or <repo>/pkg/mod.py (namespace pkg)
    mods = {m for m in re.findall(r"python -m ([A-Za-z0-9_.]+)", text)
            if m.split(".")[0] in ("repro", "benchmarks")}  # ours, not pytest
    assert mods, "README quickstart should show `python -m ...` commands"
    for mod in mods:
        rel = Path(*mod.split("."))
        candidates = [ROOT / "src" / rel.with_suffix(".py"),
                      ROOT / "src" / rel / "__init__.py",
                      ROOT / rel.with_suffix(".py"),
                      ROOT / rel / "__init__.py"]
        assert any(c.exists() for c in candidates), \
            f"README references `python -m {mod}` but no such module exists"

    # `python path/to/script.py` → the script must exist
    for script in re.findall(r"python ((?:examples|benchmarks)/[\w/]+\.py)", text):
        assert (ROOT / script).exists(), \
            f"README references `python {script}` but the file is missing"


def test_readme_mentions_tracked_benchmarks():
    text = (ROOT / "README.md").read_text()
    for record in ("BENCH_exec_time.json", "BENCH_kernels.json",
                   "BENCH_rules.json", "BENCH_stream.json",
                   "BENCH_costmodel.json", "BENCH_scaling.json"):
        assert record in text, f"README should cite {record} headline numbers"
        assert (ROOT / record).exists(), f"{record} missing from repo root"


@pytest.mark.parametrize("surface", [
    "repro.launch.mine", "repro.launch.serve_rules", "repro.launch.stream",
    "repro.launch.report",
    "examples/quickstart.py", "examples/recommend.py",
    "examples/stream_mine.py", "examples/mine_distributed.py",
    "benchmarks.bench_scaling",
])
def test_quickstart_surfaces_in_readme(surface):
    """The documented entry points stay documented."""
    assert surface in (ROOT / "README.md").read_text()


def test_matmul_kernel_family_documented():
    """The §10 counting-as-matmul subsystem stays documented: the README
    impl table, the DESIGN section, and the roofline/plan surfaces."""
    readme = (ROOT / "README.md").read_text()
    assert "Kernel implementation families" in readme
    for impl in ("matmul", "vertical_matmul", "matmul_pallas"):
        assert f"`{impl}`" in readme, f"README impl table must list {impl}"
    assert 10 in _design_sections()
    design = (ROOT / "DESIGN.md").read_text()
    for surface in ("junpack_bits", "tuned_plan", "count_kernel_roofline",
                    "count_winner", "XFER_OPS_PER_BYTE"):
        assert surface in design, f"DESIGN.md §10 must document {surface}"


def test_cluster_mesh_documented():
    """The §11 cluster-scale subsystem stays documented: the README
    distributed quickstart, the DESIGN section, and its public surfaces."""
    readme = (ROOT / "README.md").read_text()
    assert "Distributed quickstart" in readme
    for flag in ("--n-cand-shards", "--coordinator", "--balance-shards"):
        assert flag in readme, f"README distributed quickstart must show {flag}"
    assert 11 in _design_sections()
    design = (ROOT / "DESIGN.md").read_text()
    for surface in ("init_distributed", "make_mining_mesh", "choose_mesh",
                    "should_rebalance", "balance_masks", "rescatter"):
        assert surface in design, f"DESIGN.md §11 must document {surface}"


def test_multi_tenant_serving_documented():
    """The §12 multi-tenant serving layer stays documented: the README
    quickstart flags + headline, the DESIGN section, and its public
    surfaces."""
    readme = (ROOT / "README.md").read_text()
    for flag in ("--tenants", "--rate-qps", "--latency-slo-ms"):
        assert flag in readme, f"README §12 quickstart must show {flag}"
    for surface in ("RuleStore", "OpenLoopServer", "swap_rules",
                    "qps", "tests/loadgen.py"):
        assert surface in readme, f"README must document {surface}"
    assert 12 in _design_sections()
    design = (ROOT / "DESIGN.md").read_text()
    for surface in ("RuleStore", "ArenaState", "should_admit",
                    "OpenLoopServer", "swap_rules", "tag bit",
                    "qps-at-p99-SLO", "dispatch_cost_fn"):
        assert surface in design, f"DESIGN.md §12 must document {surface}"
    bench = (ROOT / "BENCH_rules.json").read_text()
    assert "open_loop" in bench and "qps_at_slo" in bench, \
        "BENCH_rules.json must carry the §12 open-loop arm"


def test_measured_policy_documented():
    """The cost-model subsystem's public surfaces stay documented: the
    `measured` algorithm row in the README table and the §9 architecture
    section it cites."""
    readme = (ROOT / "README.md").read_text()
    assert "`measured`" in readme and "BENCH_costmodel.json" in readme
    assert 9 in _design_sections()
    design = (ROOT / "DESIGN.md").read_text()
    for primitive in ("choose_width", "should_remine", "choose_fusion",
                      "should_speculate"):
        assert primitive in design, f"DESIGN.md §9 must document {primitive}"


def test_observability_documented():
    """The §13 observability layer stays documented: the README quickstart
    (trace/metrics flags, Perfetto, report + validate commands), the
    DESIGN section, and its public surfaces."""
    readme = (ROOT / "README.md").read_text()
    assert "## Observability" in readme
    for flag in ("--trace-out", "--metrics-out", "ui.perfetto.dev",
                 "repro.obs.validate", "--trace trace.json"):
        assert flag in readme, f"README Observability quickstart must show {flag}"
    assert 13 in _design_sections()
    design = (ROOT / "DESIGN.md").read_text()
    for surface in ("Tracer", "FakeClock", "MonotonicClock", "NULL_TRACER",
                    "schema_version", "validate_snapshot", "add_span",
                    "serve.query", "mine.phase", "mine.count.prep",
                    "decision."):
        assert surface in design, f"DESIGN.md §13 must document {surface}"
