"""repro.autotune: calibration sweeps, artifact store, and the AIMD
budget controller — plus the scheduler integration contracts (golden
greedy byte-equality, controlled-vs-static latency under a simulated
clock)."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.autotune import (BudgetController, CalibrationMismatchError,
                            ControllerConfig, calibrate_specs, load_table,
                            save_table, width_grid)
from repro.configs import get_config
from repro.core import GranularitySpec, TPU_V5E
from repro.core.simulate import decode_forward_cost

EPS = 0.2
SLOTS = 4
BUCKETS = (256, 1024, 4096)


def _gran(cfg):
    return GranularitySpec.for_backend(
        cfg.ffn.n_experts,
        head_dim=(cfg.attention.head_dim if cfg.attention else 128))


def _table(arch, modes=("speculative",), batch=SLOTS, buckets=BUCKETS):
    cfg = get_config(arch)
    return cfg, calibrate_specs(cfg, TPU_V5E, _gran(cfg), batch=batch,
                                modes=modes, eps=EPS, buckets=buckets)


# ===========================================================================
# Calibration sweeps
# ===========================================================================

class TestCalibrate:
    def test_entries_cover_modes_buckets(self):
        _, t = _table("stablelm_3b", modes=("greedy", "mtp"))
        assert {(e.mode, e.ell) for e in t.entries} == {
            (m, b) for m in ("greedy", "mtp") for b in BUCKETS}

    def test_calibrated_budget_clamped_to_analytic(self):
        """Calibration only refines DOWNWARD: over-prediction >= 1 on
        every entry, and the budget never leaves [1, analytic]."""
        for arch in ("stablelm_3b", "granite_moe_3b_a800m",
                     "falcon_mamba_7b", "mixtral_8x22b"):
            _, t = _table(arch)
            for e in t.entries:
                assert 1 <= e.calibrated_budget <= e.analytic_nmax, arch
                assert e.overprediction >= 1.0, arch

    def test_moe_overpredicts(self):
        """The headline: on the balanced-MoE config the analytic budget
        (tau-limited) over-predicts the measured serve-time knee —
        widening past width 1 activates experts the width-1 baseline
        never paid for."""
        _, t = _table("granite_moe_3b_a800m")
        overs = [e.overprediction for e in t.entries]
        assert max(overs) > 1.0
        # the idle-compute intuition over-predicts even harder (Table 24)
        assert all(e.idle_overprediction >= e.overprediction
                   for e in t.entries)

    def test_knee_matches_curve_tolerance(self):
        """Every width at or below the knee that was sampled satisfies
        the (1+eps) tolerance against the width-1 baseline."""
        _, t = _table("granite_moe_3b_a800m")
        for e in t.entries:
            t0 = e.times[e.ns.index(1)]
            for n, tn in zip(e.ns, e.times):
                if n <= e.measured_nmax:
                    assert tn <= (1 + EPS) * t0 + 1e-15

    def test_width_grid_covers_small_widths(self):
        ns = width_grid()
        assert set(range(1, 9)) <= set(ns)
        assert 65 in ns and 17 in ns          # one-past-tile probes


# ===========================================================================
# Artifact store
# ===========================================================================

class TestStore:
    def test_roundtrip_identical_budgets(self, tmp_path):
        _, t = _table("granite_moe_3b_a800m",
                      modes=("greedy", "speculative"))
        path = str(tmp_path / "calib.json")
        save_table(t, path)
        t2 = load_table(path, expect_key=t.key)
        assert t2.key == t.key and len(t2.entries) == len(t.entries)
        for mode in ("greedy", "speculative"):
            for ell in (1, 200, 256, 1000, 5000):
                assert (t.budget(mode, ell, False)
                        == t2.budget(mode, ell, False))
        # full numeric round-trip, not just the derived budgets
        for a, b in zip(t.entries, t2.entries):
            assert a == b

    def test_stale_key_refuses_with_clear_error(self, tmp_path):
        _, t = _table("stablelm_3b")
        path = str(tmp_path / "calib.json")
        save_table(t, path)
        with pytest.raises(CalibrationMismatchError, match="stale"):
            load_table(path, expect_key="0000000000000000")
        # loading without an expectation still works (inspection tools)
        assert load_table(path).key == t.key

    def test_key_depends_on_spec(self):
        _, t_a = _table("stablelm_3b")
        _, t_b = _table("granite_moe_3b_a800m")
        _, t_c = _table("stablelm_3b", batch=2)
        assert len({t_a.key, t_b.key, t_c.key}) == 3

    def test_schema_version_refuses(self, tmp_path):
        import json
        _, t = _table("stablelm_3b")
        path = str(tmp_path / "calib.json")
        save_table(t, path)
        data = json.load(open(path))
        data["schema"] = 999
        json.dump(data, open(path, "w"))
        with pytest.raises(CalibrationMismatchError, match="schema"):
            load_table(path)

    def test_bucket_lookup_is_conservative(self):
        _, t = _table("granite_moe_3b_a800m")
        # smallest bucket >= ell; past the last bucket, the largest
        assert t.lookup("speculative", 100, False).ell == 256
        assert t.lookup("speculative", 257, False).ell == 1024
        assert t.lookup("speculative", 10**6, False).ell == 4096
        # unknown mode falls back to any calibrated mode (the decode
        # forward is mode-independent)
        assert t.lookup("greedy", 100, False) is not None


# ===========================================================================
# BudgetController
# ===========================================================================

class TestController:
    def _controller(self, table=None, **kw):
        cfg = ControllerConfig(eps=EPS, **kw)
        c = BudgetController(table=table, config=cfg,
                             mode="speculative", use_kernel=False)
        return c

    def test_warmup_serves_width_one(self):
        c = self._controller()
        assert c.budget(100, 4, 40) == 4         # no baseline yet
        c.observe(100, 1, 1.0)
        assert c.budget(100, 4, 40) >= 4         # baseline exists now

    @given(n_active=st.integers(1, 16), analytic=st.integers(1, 256),
           lat=st.floats(0.1, 10.0), width=st.integers(1, 32))
    @settings(max_examples=100, deadline=None)
    def test_budget_always_in_bounds(self, n_active, analytic, lat, width):
        """Invariant: whatever was observed, the returned budget stays
        in [1, max(analytic, n_active)]."""
        c = self._controller()
        for i in range(4):
            b = c.budget(100, n_active, analytic)
            assert 1 <= b <= max(analytic, n_active)
            c.observe(100, width if i % 2 else 1, lat * (1 + i))

    def test_never_probes_within_cooldown(self):
        c = self._controller(patience=1, cooldown=5)
        c.observe(100, 1, 1.0)                   # baseline
        c.budget(100, 1, 64)
        # drive the width up, then force a shrink
        for _ in range(6):
            c.budget(100, 1, 64)
            c.observe(100, 1, 1.0)
        st_ = c._states[c._bucket(100)]
        assert st_.width > 1
        c.observe(100, st_.width, 100.0)         # violation -> shrink
        shrunk = st_.width
        assert st_.cooldown == 5 and st_.shrinks == 1
        # the next cooldown-1 clean steps must not probe up
        for _ in range(4):
            c.budget(100, 1, 64)
            c.observe(100, 1, 1.0)
            assert st_.width <= shrunk
        # after the window closes, probing resumes
        c.observe(100, 1, 1.0)
        assert st_.width == shrunk + 1

    def test_variance_gate_absorbs_single_spike(self):
        """patience=2: one noisy spike is gated, the width holds; a
        SECOND consecutive violation shrinks."""
        c = self._controller(patience=2)
        c.observe(100, 1, 1.0)
        for _ in range(5):
            c.budget(100, 1, 64)
            c.observe(100, 1, 1.0)
        st_ = c._states[c._bucket(100)]
        w0 = st_.width
        c.observe(100, w0, 50.0)                 # spike
        assert st_.width == w0 and st_.gated == 1 and st_.shrinks == 0
        c.observe(100, w0, 1.0)                  # clean -> streak resets
        c.observe(100, w0, 50.0)                 # spike again (isolated)
        assert st_.shrinks == 0
        c.observe(100, w0, 50.0)                 # second consecutive
        assert st_.shrinks == 1 and st_.width < w0

    def test_converges_to_stationary_width_with_table(self):
        """With a calibrated cap and in-tolerance latencies, the width
        climbs to the cap and then stays put — a stationary latency
        profile, no sawtooth."""
        _, t = _table("stablelm_3b")
        entry = t.lookup("speculative", 256, False)
        c = self._controller(table=t)
        base = entry.baseline_time
        widths = []
        for _ in range(40):
            b = c.budget(256, 1, entry.analytic_nmax)
            c.observe(256, b, base * (1 + 0.001 * b) if b > 1 else base)
            widths.append(b)
        cap = entry.calibrated_budget
        assert widths[-1] == cap
        assert all(w == cap for w in widths[-10:])

    def test_table_cap_limits_probing(self):
        """The controller never schedules a width the calibration curve
        marked above-tolerance (here: the MoE knee at width 1)."""
        cfg, t = _table("granite_moe_3b_a800m")
        g = _gran(cfg)
        clock = lambda w: decode_forward_cost(cfg, SLOTS, w, 256, g) \
            .time(TPU_V5E)
        c = BudgetController(table=t, mode="speculative", use_kernel=False)
        analytic = t.lookup("speculative", 256, False).analytic_nmax
        for _ in range(25):
            b = c.budget(200, SLOTS, analytic)
            w = max(1, b // SLOTS)
            # acceptance: the controlled loop never exceeds (1+eps)
            assert clock(w) / clock(1) <= 1 + EPS + 1e-9
            c.observe(200, w, clock(w))
        # ... while the static analytic budget demonstrably does
        w_static = max(1, analytic // SLOTS)
        assert clock(w_static) / clock(1) > 1 + EPS

    def test_aimd_recovers_when_live_knee_is_lower(self):
        """Stale-ish calibration: live latency violates AT the table
        cap; the controller shrinks below it and stays within tolerance
        thereafter (except the gated detection steps)."""
        _, t = _table("stablelm_3b")
        entry = t.lookup("speculative", 256, False)
        base = entry.baseline_time
        live_knee = 4                       # live boundary, << table cap
        clock = lambda w: base * (1.0 if w <= live_knee else 2.0)
        c = self._controller(table=t, patience=1, cooldown=10)
        widths = []
        for _ in range(60):
            b = c.budget(256, 1, entry.analytic_nmax)
            c.observe(256, b, clock(b))
            widths.append(b)
        # converged region never revisits the violating widths for long:
        # at most one probing step above the live knee per cooldown window
        tail = widths[-20:]
        assert sum(1 for w in tail if w > live_knee) <= 2
        assert c.stats()["shrinks"] >= 1

    def test_baseline_grace_falls_back_to_capped_static(self):
        """An adapter that never runs width-1 forwards (diffusion with a
        fixed block size) can never form a baseline: after the grace
        window the controller defers to the capped static budget
        instead of pinning the reported budget to n_active forever."""
        _, t = _table("stablelm_3b")              # calibrated knee 60
        grace = 4
        c = self._controller(table=t, baseline_grace=grace)
        c.bind("speculative", False, clocked=False)   # wall-clock loop,
        # simulator table -> baseline cannot seed (sources differ)
        assert c.budget(256, 4, 60) == 4          # warmup: width-1 ask
        for _ in range(grace):
            c.observe(256, 9, 1.0)                # adapter ignored it
        # fallback: capped static spend (min(60//4, 60) * 4), honest
        # telemetry instead of a forever-pinned n_active
        assert c.budget(256, 4, 60) == 60
        c_free = self._controller(baseline_grace=grace)   # no table
        for _ in range(grace):
            c_free.observe(256, 9, 1.0)
        assert c_free.budget(256, 4, 40) == 40    # analytic pass-through

    def test_stats_shape(self):
        c = self._controller()
        c.budget(100, 2, 16)
        c.observe(100, 1, 1.0)
        s = c.stats()
        assert set(s) == {"shrinks", "probes", "gated", "buckets"}
        (b,) = s["buckets"].values()
        assert {"width", "cap", "baseline_s", "noise"} <= set(b)


# ===========================================================================
# Scheduler integration (real engine; slow lane)
# ===========================================================================

@pytest.fixture(scope="module")
def tiny_setup():
    import jax
    from repro.models import init_model
    cfg = get_config("stablelm_3b", reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    prompts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i + 1), (5 + i,), 0, cfg.vocab_size))
        for i in range(3)]
    return cfg, params, prompts


@pytest.mark.slow
def test_wallclock_calibration_on_live_engine(tiny_setup):
    """The wallclock backend times real decode_slots forwards: the
    table comes back well-formed, engine state is restored, and the
    cache-headroom guards hold (width grid shrinks with max_len;
    oversized explicit grids refuse)."""
    from repro.autotune import calibrate_engine
    from repro.serving import DecodeEngine
    cfg, params, _ = tiny_setup
    eng = DecodeEngine(cfg, params, batch=2, max_len=64)
    lens_before = np.asarray(eng.slot_lens).copy()
    t = calibrate_engine(eng, modes=("greedy",), backend="wallclock",
                         ns=(1, 2), warmup=0, rounds=1, iters=1)
    assert t.backend == "wallclock"
    for e in t.entries:
        assert e.ell + 2 <= eng.max_len          # headroom held
        assert all(x > 0 for x in e.times)
        assert e.noise >= 0.0
        assert 1 <= e.calibrated_budget <= e.analytic_nmax
    assert np.array_equal(np.asarray(eng.slot_lens), lens_before)
    # default grid scales down with max_len instead of overrunning it
    t2 = calibrate_engine(eng, modes=("greedy",), backend="simulator")
    assert all(e.ell + max(e.ns) <= eng.max_len for e in t2.entries)
    with pytest.raises(ValueError, match="overruns"):
        calibrate_engine(eng, modes=("greedy",), backend="wallclock",
                         ns=(1, 63), buckets=(64,))


@pytest.mark.slow
def test_golden_greedy_controller_byte_identical(tiny_setup):
    """A ServingLoop with the BudgetController enabled must stay
    byte-identical per request to the static-budget loop in greedy
    mode: the controller reshapes budgets, never tokens."""
    from repro.serving import DecodeEngine, ServingLoop
    cfg, params, prompts = tiny_setup
    outs = []
    for controller in (None, BudgetController()):
        eng = DecodeEngine(cfg, params, batch=2, max_len=128)
        loop = ServingLoop(eng, mode="greedy", controller=controller)
        for p in prompts:
            loop.submit(p, 10)
        outs.append(loop.run())
    static, controlled = outs
    assert sorted(static) == sorted(controlled)
    for rid in static:
        assert np.array_equal(static[rid], controlled[rid]), rid


@pytest.mark.slow
def test_serving_loop_controlled_vs_static_latency(tiny_setup):
    """End-to-end acceptance on a REAL ServingLoop: with the full-size
    MoE config's simulated clock injected, the static analytic budget
    exceeds the (1+eps) latency tolerance while the calibrated
    controller never does — and the step_log carries the full budget
    provenance."""
    import jax
    from repro.models import init_model
    from repro.serving import DecodeEngine, ServingLoop
    arch = "granite_moe_3b_a800m"
    cfg_full, table = _table(arch)
    g = _gran(cfg_full)

    def clock(width, ell):
        bucket = table.lookup(None, ell).ell
        return decode_forward_cost(cfg_full, SLOTS, width, bucket,
                                   g).time(TPU_V5E)

    red = get_config(arch, reduced=True)
    params = init_model(jax.random.PRNGKey(0), red)
    ratios = {}
    for name, controller in (("static", None),
                             ("controlled", BudgetController(table=table))):
        eng = DecodeEngine(red, params, batch=SLOTS, max_len=MAX_LEN_T)
        loop = ServingLoop(eng, mode="speculative", eps=EPS,
                           controller=controller, step_clock=clock)
        for i in range(4):
            prompt = np.asarray(jax.random.randint(
                jax.random.PRNGKey(50 + i), (6,), 0, red.vocab_size))
            loop.submit(prompt, 8)
        loop.run()
        ratios[name] = max(clock(e["width"], e["ell"])
                           / clock(1, e["ell"]) for e in loop.step_log)
        for e in loop.step_log:
            assert "budget_analytic" in e and "ell" in e
        if controller is not None:
            s = loop.stats()
            assert "controller" in s
            assert s.get("max_latency_ratio", 1.0) <= 1 + EPS + 1e-6
            assert any("budget_calibrated" in e for e in loop.step_log)
    assert ratios["static"] > 1 + EPS
    assert ratios["controlled"] <= 1 + EPS + 1e-9


MAX_LEN_T = 128


def test_budget_floor_regression_fractional_boundary():
    """Satellite regression: the deployment budget FLOORS a fractional
    boundary (rounding up would spend one position past the knee).
    At b=9 on TPU v5e the dense idle term is rho*s/(2b) ~= 26.73."""
    from repro.core import parallelism_budget, predict_model
    cfg = get_config("stablelm_3b")
    g = _gran(cfg)
    pred = predict_model(cfg, TPU_V5E, g, b=9, ell=256)
    assert pred.n_max != int(pred.n_max)          # genuinely fractional
    assert round(pred.n_max) > math.floor(pred.n_max)   # would round UP
    assert parallelism_budget(cfg, TPU_V5E, g, b=9, ell=256) \
        == math.floor(pred.n_max)
