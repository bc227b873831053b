"""The repro.analysis static analyzer: fixture-driven checker behaviour
(bad patterns flagged, clean idioms silent), the real tree vs. its
committed baseline, and the compile-discipline regression the analyzer
exists to protect (steady-state serving must never recompile).

Fast lane: fixture projects are tiny tmp_path packages parsed by the
AST index directly; the Pallas capture harness runs the real kernels'
ops entries eagerly on CPU in ~2s (module-scoped)."""
import json
import math
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (Project, diff_against_baseline, load_baseline,
                            run_checkers, write_baseline)
from repro.analysis import baseline as baseline_mod
from repro.analysis import host_sync, pallas_contracts, recompile
from repro.analysis.cli import find_repo_root, main
from repro.analysis.findings import Finding
from repro.analysis.granularity_drift import (check_drift, declared_tiles,
                                              launched_tiles)
from repro.analysis.pallas_contracts import (KERNEL_PATHS, CapturedLaunch,
                                             CaptureTarget,
                                             capture_launches, check_launch)
from repro.configs import get_config
from repro.kernels.decode_attention import ops as attn_ops
from repro.models import init_model
from repro.serving import DecodeEngine, ServingLoop
from repro.serving.engine import _decode_fn

ROOT = find_repo_root(Path(__file__).resolve().parent)


# ===========================================================================
# fixture projects
# ===========================================================================

def make_project(tmp_path, **modules) -> Project:
    src = tmp_path / "src"
    pkg = src / "pkg"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "__init__.py").write_text("")
    for name, code in modules.items():
        (pkg / f"{name}.py").write_text(textwrap.dedent(code))
    return Project(src, rel_to=tmp_path)


BAD_LOOP = '''
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def model_step(x):
        return x + 1

    class Loop:
        def __init__(self):
            self.state = jnp.zeros((4,))

        def step(self):
            y = model_step(self.state)
            n = int(y[0])
            host = np.asarray(y)
            vals = y.tolist()
            acc = 0.0
            for v in y:
                acc += 1.0
            jax.block_until_ready(y)
            return n, host, vals, acc
'''

CLEAN_LOOP = '''
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def model_step(x):
        return x + 1

    class Loop:
        def __init__(self):
            self.state = jnp.zeros((4,))
            self.count = 0

        def step(self):
            y = model_step(self.state)
            self.state = y
            self.count += 1
            width = int(jnp.shape(y)[0])
            meta = (y.shape, y.dtype)
            host_tokens = np.zeros((width,), np.int32)
            return width, meta, host_tokens
'''

PRAGMA_LOOP = '''
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def model_step(x):
        return x + 1

    class Loop:
        def __init__(self):
            self.state = jnp.zeros((4,))

        def step(self):
            y = model_step(self.state)
            sanctioned = np.asarray(y)  # analysis: allow-hs002
            bad = np.asarray(y)
            return sanctioned, bad
'''

BAD_HAZARD = '''
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, static_argnames=("width",))
    def forward(tokens, width):
        return tokens[:, :width]

    def serve(prompts):
        outs = []
        for p in prompts:
            fn = jax.jit(lambda x: x + 1)
            n = len(p)
            toks = np.zeros((1, n), np.int32)
            outs.append(forward(jnp.asarray(toks), width=n))
        return outs
'''

CLEAN_HAZARD = '''
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, static_argnames=("width",))
    def forward(tokens, width):
        return tokens[:, :width]

    def prefill_bucket(n):
        w = 8
        while w < n:
            w *= 2
        return w

    def serve(prompts):
        outs = []
        for p in prompts:
            width = prefill_bucket(len(p))
            toks = np.zeros((1, width), np.int32)
            outs.append(forward(jnp.asarray(toks), width=width))
        return outs
'''

FIXTURE_ROOTS = ("pkg.loop.Loop.step",)


# ===========================================================================
# checker 1: host-sync
# ===========================================================================

def test_host_sync_flags_every_sync_family(tmp_path):
    project = make_project(tmp_path, loop=BAD_LOOP)
    findings = host_sync.check(project, roots=FIXTURE_ROOTS)
    rules = {f.rule for f in findings}
    assert rules == {"HS001", "HS002", "HS003", "HS004", "HS005"}
    assert all(f.path == "src/pkg/loop.py" for f in findings)
    assert all(f.symbol == "pkg.loop.Loop.step" for f in findings)


def test_host_sync_clean_loop_zero_false_positives(tmp_path):
    project = make_project(tmp_path, loop=CLEAN_LOOP)
    assert host_sync.check(project, roots=FIXTURE_ROOTS) == []


def test_host_sync_only_hot_path_is_checked(tmp_path):
    """The same sync outside the reachable set is not the hot path's
    problem — reachability, not a whole-tree grep."""
    project = make_project(tmp_path, loop=CLEAN_LOOP, offline=BAD_LOOP)
    assert host_sync.check(project, roots=FIXTURE_ROOTS) == []
    via_offline = host_sync.check(project, roots=("pkg.offline.Loop.step",))
    assert {f.rule for f in via_offline} == {"HS001", "HS002", "HS003",
                                            "HS004", "HS005"}


def test_host_sync_pragma_suppresses_sanctioned_line(tmp_path):
    project = make_project(tmp_path, loop=PRAGMA_LOOP)
    findings = host_sync.check(project, roots=FIXTURE_ROOTS)
    assert len(findings) == 1
    (f,) = findings
    assert f.rule == "HS002" and "bad = " not in f.snippet
    src = (tmp_path / "src/pkg/loop.py").read_text()
    bad_line = next(i for i, t in enumerate(src.splitlines(), 1)
                    if t.strip().startswith("bad ="))
    assert f.line == bad_line


# ===========================================================================
# checker 2: recompile hazards
# ===========================================================================

def test_recompile_flags_jit_in_body_and_shape_derived_args(tmp_path):
    project = make_project(tmp_path, hazard=BAD_HAZARD)
    findings = recompile.check(project)
    rules = {f.rule for f in findings}
    assert rules == {"RH001", "RH002", "RH003"}
    by_rule = {f.rule: f for f in findings}
    assert "jax.jit" in by_rule["RH001"].snippet
    assert "width" in by_rule["RH002"].message
    assert "tokens" in by_rule["RH003"].message


def test_recompile_bucketing_cleanses_shape_taint(tmp_path):
    """prefill_bucket(len(p)) is the sanctioned laundering of a runtime
    length into a small compile set — zero findings."""
    project = make_project(tmp_path, hazard=CLEAN_HAZARD)
    assert recompile.check(project) == []


def test_recompile_own_jit_decorator_not_flagged(tmp_path):
    """A module-scope @functools.partial(jax.jit, ...) decorator is the
    CORRECT idiom and must not self-flag as RH001."""
    project = make_project(tmp_path, hazard=CLEAN_HAZARD)
    assert not [f for f in recompile.check(project) if f.rule == "RH001"]


# ===========================================================================
# checker 3: Pallas launch contracts (handcrafted captures)
# ===========================================================================

class _Spec:
    """Minimal BlockSpec stand-in (block_shape + index_map attrs)."""

    def __init__(self, block_shape, index_map=None):
        self.block_shape = block_shape
        self.index_map = index_map


def _launch(**kw) -> CapturedLaunch:
    base = dict(
        label="fixture", kernel_path="fixture.py", kernel_name="k",
        grid=(2,), num_scalar_prefetch=0,
        in_specs=[_Spec((8, 128), lambda i: (i, 0))],
        out_specs=[_Spec((8, 128), lambda i: (i, 0))],
        in_shapes=[(16, 128)], out_shapes=[(16, 128)],
        prefetch_values=[], kernel_params=2, scratch_count=0)
    base.update(kw)
    return CapturedLaunch(**base)


def test_contract_good_launch_is_clean():
    assert check_launch(_launch()) == []


def test_contract_out_of_bounds_index_map():
    # grid walks 4 steps over a 2-block operand: blocks 2 and 3 read
    # past the buffer
    findings = check_launch(_launch(grid=(4,)))
    assert findings and {f.rule for f in findings} == {"PK004"}
    assert "out of bounds" in findings[0].message


def test_contract_operand_spec_arity_mismatch():
    findings = check_launch(_launch(in_shapes=[(16, 128), (4,)]))
    assert [f.rule for f in findings] == ["PK001"]


def test_contract_kernel_ref_count_mismatch():
    findings = check_launch(_launch(kernel_params=5))
    assert [f.rule for f in findings] == ["PK002"]


def test_contract_index_map_wrong_rank():
    bad = _Spec((8, 128), lambda i: (i,))
    findings = check_launch(_launch(in_specs=[bad]))
    assert findings and findings[0].rule == "PK003"


def test_contract_index_map_raise_is_pk003():
    def boom(i):
        raise ValueError("corrupt block table")
    findings = check_launch(_launch(in_specs=[_Spec((8, 128), boom)]))
    assert findings and findings[0].rule == "PK003"
    assert "ValueError" in findings[0].message


def test_contract_indivisible_block_is_pk005():
    findings = check_launch(_launch(
        in_specs=[_Spec((8, 128), lambda i: (min(i, 2), 0))],
        in_shapes=[(20, 128)]))
    assert [f.rule for f in findings] == ["PK005"]


def test_contract_prefetch_values_feed_index_maps():
    """Scalar-prefetch arrays are passed to index maps by VALUE — a
    map reading a real sequence length stays in bounds, one reading a
    corrupt length walks out."""
    lens_ok = [np.asarray([1], np.int32)]
    lens_bad = [np.asarray([9], np.int32)]
    out = _Spec((8, 128), lambda i, lens: (i, 0))
    spec = _Spec((8, 128), lambda i, lens: (min(int(lens[0]), 1) + i - i, 0))
    good = _launch(num_scalar_prefetch=1, prefetch_values=lens_ok,
                   in_specs=[spec], out_specs=[out], kernel_params=3)
    assert check_launch(good) == []
    raw = _Spec((8, 128), lambda i, lens: (int(lens[0]), 0))
    bad = _launch(num_scalar_prefetch=1, prefetch_values=lens_bad,
                  in_specs=[raw], out_specs=[out], kernel_params=3)
    assert [f.rule for f in check_launch(bad)] == ["PK004"]


# ===========================================================================
# checker 4: granularity drift
# ===========================================================================

_TILES = {"m_attn_decode": 64, "k_block": 128}


def test_drift_clean_when_all_three_agree():
    assert check_drift(dict(_TILES), declared=dict(_TILES),
                       launched=dict(_TILES)) == []


def test_drift_declared_vs_contract_is_gd001():
    declared = dict(_TILES, m_attn_decode=32)
    findings = check_drift(dict(_TILES), declared=declared,
                           launched=declared)
    assert [f.rule for f in findings] == ["GD001"]
    assert findings[0].symbol == "m_attn_decode"


def test_drift_launched_vs_declared_is_gd002():
    launched = dict(_TILES, k_block=256)
    findings = check_drift(dict(_TILES), declared=dict(_TILES),
                           launched=launched)
    assert [f.rule for f in findings] == ["GD002"]
    assert findings[0].symbol == "k_block"


def test_drift_unpinned_knob_is_gd003():
    findings = check_drift({}, declared=dict(_TILES),
                           launched=dict(_TILES))
    assert {f.rule for f in findings} == {"GD003"}
    assert len(findings) == len(_TILES)


def test_drift_findings_are_never_baseline_suppressible():
    findings = check_drift(dict(_TILES),
                           declared=dict(_TILES, m_attn_decode=32),
                           launched=dict(_TILES))
    bl = {"suppressions": {f.fingerprint: {"count": 99} for f in findings}}
    new, suppressed, _ = diff_against_baseline(findings, bl)
    assert new == findings and suppressed == []


# ===========================================================================
# baseline mechanics
# ===========================================================================

def _finding(line=3, snippet="int(y)"):
    return Finding("host-sync", "HS001", "src/pkg/loop.py", line,
                   "pkg.loop.Loop.step", "msg", snippet)


def test_fingerprint_is_line_number_independent():
    assert _finding(line=3).fingerprint == _finding(line=99).fingerprint
    assert (_finding(snippet="int(y)").fingerprint
            != _finding(snippet="int(z)").fingerprint)


def test_baseline_roundtrip_suppresses_known_debt(tmp_path):
    path = tmp_path / "analysis-baseline.json"
    write_baseline(path, [_finding()], {"m_attn_decode": 64})
    bl = load_baseline(path)
    assert bl["granularity_contract"] == {"m_attn_decode": 64}
    new, suppressed, stale = diff_against_baseline([_finding(line=7)], bl)
    assert new == [] and len(suppressed) == 1 and stale == []


def test_baseline_counts_gate_duplicate_snippets(tmp_path):
    path = tmp_path / "analysis-baseline.json"
    write_baseline(path, [_finding()], {})
    bl = load_baseline(path)
    new, suppressed, _ = diff_against_baseline(
        [_finding(line=3), _finding(line=9)], bl)
    assert len(suppressed) == 1 and len(new) == 1


def test_baseline_reports_stale_entries_when_debt_is_fixed(tmp_path):
    path = tmp_path / "analysis-baseline.json"
    write_baseline(path, [_finding()], {})
    _, _, stale = diff_against_baseline([], load_baseline(path))
    assert len(stale) == 1 and stale[0]["rule"] == "HS001"


# ===========================================================================
# CLI gate on fixture trees
# ===========================================================================

def _fixture_repo(tmp_path, code) -> Path:
    (tmp_path / "pyproject.toml").write_text("[project]\nname='fixture'\n")
    pkg = tmp_path / "src" / "pkg"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "loop.py").write_text(textwrap.dedent(code))
    return tmp_path


def test_cli_check_baseline_fails_on_bad_fixture_tree(tmp_path, capsys):
    root = _fixture_repo(tmp_path, BAD_LOOP)
    rc = main(["--root", str(root),
               "--checkers", "host-sync,recompile-hazard",
               "--roots", "pkg.loop.Loop.step", "--check-baseline"])
    assert rc == 2
    assert "FAIL" in capsys.readouterr().err


def test_cli_check_baseline_passes_on_clean_fixture_tree(tmp_path, capsys):
    root = _fixture_repo(tmp_path, CLEAN_LOOP)
    rc = main(["--root", str(root),
               "--checkers", "host-sync,recompile-hazard",
               "--roots", "pkg.loop.Loop.step", "--check-baseline"])
    assert rc == 0
    assert "0 new" in capsys.readouterr().out


def test_cli_json_output_is_machine_readable(tmp_path, capsys):
    root = _fixture_repo(tmp_path, BAD_LOOP)
    rc = main(["--root", str(root), "--checkers", "host-sync",
               "--roots", "pkg.loop.Loop.step", "--json"])
    assert rc == 0
    data = json.loads(capsys.readouterr().out)
    rules = {f["rule"] for f in data["findings"]}
    assert {"HS001", "HS002"} <= rules
    assert all(f["fingerprint"] for f in data["findings"])


# ===========================================================================
# the real tree vs. its committed baseline
# ===========================================================================

@pytest.fixture(scope="module")
def captures():
    return capture_launches()


@pytest.fixture(scope="module")
def tree_project():
    return Project(ROOT / "src", rel_to=ROOT)


def test_committed_baseline_is_current(captures):
    """`python -m repro.analysis --check-baseline` must pass on this
    tree: no NEW findings, no stale suppressions."""
    bl = load_baseline(ROOT / baseline_mod.BASELINE_NAME)
    findings = run_checkers(ROOT / "src", rel_to=ROOT,
                            contract=bl["granularity_contract"],
                            captures=captures)
    new, _, stale = diff_against_baseline(findings, bl)
    assert new == [], "\n".join(f.render() for f in new)
    assert stale == [], stale


def test_real_kernel_launches_satisfy_contracts(captures):
    assert len(captures) >= 6
    findings = pallas_contracts.check(captures=captures)
    assert findings == [], "\n".join(f.render() for f in findings)


def _paged_below_all_heads():
    """A paged launch whose shapes give fewer heads a step than KV heads:
    64 query heads over 8 KV heads of 128, bf16, width 65."""
    n_phys, bs, kv, dh = 5, 128, 8, 128
    q = jnp.zeros((1, 65, 64, dh), jnp.bfloat16)
    pool = jnp.zeros((2, kv, n_phys, dh, bs), jnp.bfloat16)
    attn_ops.decode_attention_paged(
        q, pool, pool, jnp.asarray([100], jnp.int32),
        jnp.asarray([[0, 1, 2, 4]], jnp.int32), 1)


@pytest.mark.parametrize("case", ["harness", "below_all_heads"])
def test_slack_report_matches_captured_paged_launch(captures, case):
    """``ops.slack_report``'s ``heads_per_step`` and ``grid_steps`` are
    the head block and the grid the paged launch is captured with: the
    capture harness's paged example (float32, every head a step), and
    one whose shapes give half the KV heads a step."""
    if case == "harness":
        launch = {c.label: c for c in captures}["decode_attention_paged/n1"]
        n, itemsize = 1, 4
    else:
        launch, = capture_launches([CaptureTarget(
            "decode_attention_paged/n65", KERNEL_PATHS["decode_attention"],
            _paged_below_all_heads)])
        assert check_launch(launch) == []
        n, itemsize = 65, 2
    lens, tables, _ = launch.prefetch_values
    _, kv, g, _, dh = launch.in_shapes[0]
    bs = launch.in_shapes[1][-1]
    rep = attn_ops.slack_report(n, lens, tables.shape[1] * bs, head_dim=dh,
                                k_block=bs, n_heads=kv * g, kv_heads=kv,
                                itemsize=itemsize)
    hps = launch.in_specs[0].block_shape[1]
    assert launch.in_specs[1].block_shape[1] == hps
    assert rep["heads_per_step"] == hps
    assert rep["grid_steps"] == math.prod(launch.grid)
    assert (hps == kv) == (case == "harness")


def test_launched_tiles_match_granularity_registry(captures):
    """The block shapes kernels ACTUALLY launch with are the numbers
    core.granularity hands the Eq. 12-14 predictor."""
    declared, launched = declared_tiles(), launched_tiles(captures)
    assert set(launched) == {"m_attn_decode", "m_moe_decode", "m_ssm",
                             "k_block"}
    for knob, got in launched.items():
        assert declared[knob] == got, knob


def test_committed_contract_pins_declared_tiles():
    bl = load_baseline(ROOT / baseline_mod.BASELINE_NAME)
    assert bl["granularity_contract"] == declared_tiles()


def test_one_sided_tile_change_fails_drift_check(captures):
    """Acceptance gate: halving a declared tile WITHOUT updating the
    pinned contract (or the kernels) must fail, un-suppressibly."""
    bl = load_baseline(ROOT / baseline_mod.BASELINE_NAME)
    declared = declared_tiles()
    declared["m_attn_decode"] //= 2
    findings = check_drift(bl["granularity_contract"], declared=declared,
                           launched=launched_tiles(captures))
    rules = {f.rule for f in findings}
    assert "GD001" in rules      # declared walked off the contract
    assert "GD002" in rules      # ...and off what kernels launch
    new, _, _ = diff_against_baseline(
        findings,
        {"suppressions": {f.fingerprint: {"count": 9} for f in findings}})
    assert new == findings


def test_serving_hot_path_has_no_unsanctioned_syncs(tree_project):
    """Satellite verification: after the host-mirror and on-device
    argmax fixes, no unsanctioned hot-path sync is left.  The admission
    argmax no longer appears: admission moved OUT of the step hot path
    (``admit`` runs at the arrival boundary, batching first-token
    readback), which drained its HS001 baseline entry; the diffusion
    adapters read back on-device argmax and confidence, not logits,
    which drained the per-row logits pull (HS002)."""
    findings = host_sync.check(tree_project)
    symbols = {f.symbol for f in findings}
    fixed = {
        "repro.serving.engine.DecodeEngine.decode_slots",
        "repro.serving.engine.DecodeEngine.commit_slots",
        "repro.serving.engine.DecodeEngine.prefill_slots",
        "repro.serving.scheduler.ServingLoop.step",
        "repro.serving.scheduler.ServingLoop.budget",
        "repro.serving.scheduler.ServingLoop._admit",
        "repro.serving.scheduler.ServingLoop.admit",
        "repro.serving.mtp.MTPSlotAdapter.run_step",
        "repro.serving.algorithm.GreedySlotAdapter.run_step",
    }
    assert not (symbols & fixed), sorted(symbols & fixed)
    assert symbols == set(), sorted(symbols)


# ===========================================================================
# compile discipline: steady-state serving never recompiles
# ===========================================================================

def test_steady_state_decode_zero_recompiles():
    """After warmup, more decode steps — including a mid-stream
    admission — must add ZERO entries to the decode jit cache (the
    regression the recompile-hazard checker guards statically)."""
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(50 + i), (5 + i,), 0, cfg.vocab_size))
        for i in range(4)]
    eng = DecodeEngine(cfg, params, batch=4, max_len=96)
    loop = ServingLoop(eng, mode="greedy")
    for p in prompts[:2]:
        loop.submit(p, 12)
    for _ in range(3):
        loop.admit()
        loop.step()
    warm = _decode_fn._cache_size()
    assert warm > 0
    for p in prompts[2:]:
        loop.submit(p, 12)
    while True:
        loop.admit()
        if not loop.step():
            break
    assert _decode_fn._cache_size() == warm
    assert len(loop.finished) == 4
