"""spflint gate: the static passes themselves.

Three layers, mirroring how the tool is trusted in CI:

1. **Seeded violations** — `tests/fixtures/spflint/badpkg/` plants one
   violation per rule ID, each marked in-line with ``# expect: SPF...``;
   the passes must report EXACTLY that (file, line, rule) multiset.
2. **Clean-tree gate** — `python -m repro.analysis src` semantics: the
   shipped tree has zero findings, the baseline stays empty, and the
   VMEM pass covers 100% of the ``pl.pallas_call`` sites in
   ``src/repro/kernels/``.
3. **Parity** — the analyzer's static VMEM estimate for one real
   ``posting_scan`` configuration must equal the bytes computed from
   actual operand arrays at the reference shape (and the kernel must
   actually run at those shapes).

Plus the runtime half of the lock discipline: ``install_lock_check``
must reject exactly the writes the ownership map forbids.
"""
import ast
import json
import re
import threading
from pathlib import Path

import numpy as np
import pytest

pytestmark = pytest.mark.gate

from repro.analysis import run_all
from repro.analysis.__main__ import main as spflint_main
from repro.analysis.common import (
    RULES,
    load_baseline,
    parse_tree,
    split_by_baseline,
    write_baseline,
)
from repro.analysis.config import (
    VMEM_BINDINGS,
    AnalysisSpec,
    LockSpec,
    ReplaySpec,
    VmemSpec,
)
from repro.serve.ownership import (
    GUARDED,
    INIT,
    LIFECYCLE,
    PUMP,
    CheckedRLock,
    LockDisciplineError,
    install_lock_check,
)

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src"
FIXTURES = Path(__file__).resolve().parent / "fixtures" / "spflint"

# The fixture twin of config.DEFAULT_SPEC: same passes, aimed at badpkg.
FIXTURE_SPEC = AnalysisSpec(
    replay=ReplaySpec(
        roots=("badpkg.steps:build_step",),
        config_class="badpkg.types:Cfg",
        critical_stamp="badpkg.stamps:REPLAY_CRITICAL_FIELDS",
        exempt_stamp="badpkg.stamps:REPLAY_EXEMPT_FIELDS",
    ),
    locks=LockSpec(module_prefixes=("badpkg.serve",)),
    vmem=VmemSpec(
        module_prefixes=("badpkg.kern",),
        budget_bytes=16 * 1024 * 1024,
        bindings={"dim": 128},
        dtype_overrides={},
    ),
)

_MARKER = re.compile(r"#\s*expect:\s*([A-Z0-9 ]+)$")


def _expected_markers() -> list[tuple[str, int, str]]:
    """(rel-file, line, rule) for every ``# expect:`` marker token."""
    out = []
    for path in sorted(FIXTURES.rglob("*.py")):
        rel = path.relative_to(FIXTURES.parent).as_posix()
        for lineno, text in enumerate(path.read_text().splitlines(), 1):
            m = _MARKER.search(text)
            if m:
                out.extend((rel, lineno, r) for r in m.group(1).split())
    return out


# ---------------------------------------------------------------------------
# 1. Seeded violations: exact (file, line, rule) agreement
# ---------------------------------------------------------------------------

def test_seeded_fixtures_report_exact_findings():
    result = run_all(FIXTURES, spec=FIXTURE_SPEC)
    got = sorted((f.file, f.line, f.rule) for f in result["findings"])
    want = sorted(_expected_markers())
    assert got == want, (
        "spflint findings diverge from the seeded # expect markers:\n"
        f"  missing: {sorted(set(want) - set(got))}\n"
        f"  extra:   {sorted(set(got) - set(want))}"
    )
    # every rule in the registry is exercised by at least one seed
    assert {r for _, _, r in want} == set(RULES)


def test_fixture_baseline_roundtrip(tmp_path):
    findings = run_all(FIXTURES, spec=FIXTURE_SPEC)["findings"]
    assert findings
    path = tmp_path / "baseline.json"
    write_baseline(path, findings)
    new, suppressed = split_by_baseline(findings, load_baseline(path))
    assert new == [] and len(suppressed) == len(findings)
    # keys are (rule, file, symbol) — line-stable on edits above the site
    entry = json.loads(path.read_text())["suppressions"][0]
    assert set(entry) == {"rule", "file", "symbol", "reason"}


# ---------------------------------------------------------------------------
# 2. Clean-tree gate + 100% pallas_call coverage
# ---------------------------------------------------------------------------

def _count_pallas_sites() -> int:
    n = 0
    for path in sorted((SRC / "repro" / "kernels").rglob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"
            ):
                n += 1
    return n


def test_shipped_tree_is_clean():
    result = run_all(SRC)
    assert [f.render() for f in result["findings"]] == []


def test_shipped_baseline_is_empty():
    assert load_baseline(REPO / "tools" / "spflint_baseline.json") == set()


def test_vmem_pass_covers_every_pallas_call_site():
    result = run_all(SRC)
    n_sites = _count_pallas_sites()
    assert n_sites >= 7
    assert len(result["vmem_table"]) == n_sites
    budget = result["vmem_budget_mib"] * 1024 * 1024
    for row in result["vmem_table"]:
        assert row["vmem_bytes"] <= budget, row


def test_cli_exit_codes(tmp_path, capsys):
    report = tmp_path / "report.json"
    rc = spflint_main([
        str(SRC),
        "--baseline", str(REPO / "tools" / "spflint_baseline.json"),
        "--json", str(report),
    ])
    assert rc == 0
    data = json.loads(report.read_text())
    assert data["summary"]["new"] == 0
    assert data["summary"]["kernels_analyzed"] == _count_pallas_sites()
    assert data["rules"] == RULES

    assert spflint_main([str(tmp_path / "no_such_tree")]) == 2

    assert spflint_main(["--rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


# ---------------------------------------------------------------------------
# 3. VMEM estimate vs actual shapes: one real posting_scan configuration
# ---------------------------------------------------------------------------

def test_vmem_estimate_matches_actual_scan_batched_topk_shapes():
    """The static estimate for ``scan_batched_topk`` must equal the bytes
    of the real operand blocks at the reference serving shape — and the
    kernel must actually accept operands of those shapes."""
    import jax.numpy as jnp

    from repro.kernels.posting_scan.kernel import scan_batched_topk

    result = run_all(SRC)
    (row,) = [
        r for r in result["vmem_table"]
        if r["kernel"] == "scan_batched_topk"
    ]

    b = VMEM_BINDINGS
    q_n, dim, bs, k = b["q_n"], b["dim"], b["bs"], b["k"]

    # the real per-grid-step blocks, from the wrapper's BlockSpecs
    rng = np.random.default_rng(0)
    queries = rng.standard_normal((q_n, dim)).astype(np.float32)
    blocks = rng.integers(-127, 128, (8, bs, dim)).astype(np.int8)
    slot_bias = np.zeros((8, bs), np.float32)
    expect = [
        ("in", (q_n, dim), queries.itemsize),         # resident queries
        ("in", (1, bs, dim), blocks.itemsize),        # one streamed page
        ("in", (1, bs, 1), slot_bias.itemsize),       # liveness bias column
        ("out", (1, k, q_n), np.dtype(np.float32).itemsize),
        ("out", (1, k, q_n), np.dtype(np.int32).itemsize),
    ]
    got = [(o["role"], tuple(o["shape"])) for o in row["operands"]]
    assert got == [(r, s) for r, s, _ in expect]
    manual = 2 * sum(int(np.prod(s)) * isz for _, s, isz in expect)
    assert row["vmem_bytes"] == manual
    assert tuple(row["grid"]) == (b["nb"],)

    # the wrapper really runs at these shapes (nb shrunk to keep the
    # interpret-mode run cheap; per-block shapes are nb-independent)
    kd, ki = scan_batched_topk(
        jnp.arange(8, dtype=jnp.int32), jnp.asarray(queries),
        jnp.asarray(blocks), jnp.asarray(slot_bias),
        k=k, interpret=True,
    )
    assert kd.shape == (8, k, q_n) and ki.shape == (8, k, q_n)
    assert bool(jnp.isfinite(kd).all()) and int(ki.max()) < bs


# ---------------------------------------------------------------------------
# Runtime lock checker (the dynamic half of the SPF20x discipline)
# ---------------------------------------------------------------------------

class _Dummy:
    FIELD_OWNERSHIP = {
        "_work": INIT,
        "cfg": INIT,
        "_inflight": GUARDED,
        "_busy": PUMP,
        "_pump_thread": LIFECYCLE,
    }

    def __init__(self):
        self._work = threading.RLock()
        self.cfg = 1
        self._inflight = 0
        self._busy = False
        self._pump_thread = None


def test_runtime_lock_check_enforces_ownership():
    d = _Dummy()
    install_lock_check(d)
    assert isinstance(d._work, CheckedRLock)

    with pytest.raises(LockDisciplineError, match="guarded"):
        d._inflight = 1
    with d._work:
        d._inflight = 2                   # guarded write under the lock
    assert d._inflight == 2

    with pytest.raises(LockDisciplineError, match="init-only"):
        d.cfg = 99

    d._busy = True                        # no live pump thread: allowed
    d._pump_thread = None                 # not on the pump thread: allowed

    # pump-only field from a foreign thread while the pump is "alive"
    # (main thread plays the pump: it is certainly alive)
    object.__setattr__(d, "_pump_thread", threading.current_thread())
    try:
        err = []

        def foreign():
            try:
                d._busy = False
            except LockDisciplineError as e:
                err.append(e)

        ft = threading.Thread(target=foreign)
        ft.start()
        ft.join()
        assert err and "pump-thread-only" in str(err[0])
        d._busy = False                   # ...but the "pump" thread may
    finally:
        object.__setattr__(d, "_pump_thread", None)

    # escape hatch tests rely on: bypasses the checker entirely
    object.__setattr__(d, "cfg", 7)
    assert d.cfg == 7

    install_lock_check(d)                 # idempotent
    assert type(d).__name__ == "_DummyLockChecked"


def test_checked_rlock_tracks_owner():
    lk = CheckedRLock()
    assert not lk.held_by_me
    with lk:
        assert lk.held_by_me
        with lk:                          # re-entrant
            assert lk.held_by_me
        assert lk.held_by_me
    assert not lk.held_by_me


def test_fixture_tree_parses_under_expected_names():
    mods = parse_tree(FIXTURES)
    assert {
        "badpkg", "badpkg.types", "badpkg.stamps", "badpkg.steps",
        "badpkg.serve_bad", "badpkg.kern_bad",
    } <= set(mods)


def test_pytest_never_collects_the_fixture_tree():
    """pytest.ini pins ``norecursedirs = tests/fixtures/spflint``: the
    seeded-violation package is broken ON PURPOSE, so pytest must never
    recurse into it — a ``test_*.py`` landing there would otherwise be
    imported at collection time and take the whole suite down.  Run a
    real collection pass over tests/ and assert the pin holds."""
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "--co", "-p", "no:cacheprovider", "tests/fixtures"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    # exit code 5 = "no tests collected" — exactly what the pin demands
    assert proc.returncode == 5, proc.stdout + proc.stderr
    assert "spflint" not in proc.stdout
