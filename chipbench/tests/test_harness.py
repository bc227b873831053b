"""A whole run at toy size on the CPU (the look for a chip skipped):
sound runs come out correct, and each fault a serving cell can have,
planted in the timed path, comes out not correct.  Off the chip the
command refuses and prints no result."""
import argparse
import json
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
import trace_reduce
from conftest import BENCH, REPO

SEED = 2**33 + 11

run.import_program()


def _run(root, seconds=2.0, seed=SEED):
    args = argparse.Namespace(workload="tiny.cell", seed=seed,
                              seconds=seconds, trace=0)
    return run.run_cell(args, root=root, require_tpu=False)


@pytest.mark.parametrize("config,mix", [("tiny_dense", "tiny.closed"),
                                        ("tiny_moe", "tiny.open")])
def test_sound_run_is_correct(tiny_root, config, mix):
    res = _run(tiny_root(config, mix))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["metrics"]["output_tok_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"



def test_trace_covers_the_last_seconds_of_the_window(tiny_root, tmp_path):
    """The profiler runs over the window's last ``trace_s`` seconds, so
    that its slow stop falls after the window's close."""
    root = tiny_root("tiny_moe", "tiny.open")
    cell = run.load_cell("tiny.cell", root)
    config, mix = cell["config"], cell["mix"]
    run.import_program(root)
    engine = run.build_engine(config, SEED, run.model_of(cell))
    run.warm_up(engine, mix, config, SEED)
    requests, _ = run.plan(mix, config, SEED, 2.0, engine.cfg.vocab_size)
    driver = run.Driver(run.new_loop(engine, mix, config), mix, requests, 0)
    got = driver.serve(0.5, 2.0, 0.5, str(tmp_path / "trace"))
    t0, t1 = got.trace_t
    assert got.w1 - 0.5 <= t0 < got.w1 <= t1
    assert trace_reduce.find_xplane(str(tmp_path / "trace"))

def _alter_tokens(monkeypatch):
    """A token altered where it is produced: every decode winner + 1."""
    import repro.serving.algorithm as algorithm
    real = algorithm.greedy_tokens
    monkeypatch.setattr(algorithm, "greedy_tokens",
                        lambda logits: (real(logits) + 1) % logits.shape[-1])


def _stale_state(monkeypatch):
    """A step that returns its state unchanged: the new KV is dropped.
    The forward donates the pool and adopts the one it returns, so the
    fault keeps a copy of the pool from before the forward and hands
    that back in its place, for the commit to adopt."""
    from repro.serving.engine import DecodeEngine
    real = DecodeEngine.decode_slots

    def decode_slots(self, tokens):
        before = jax.tree.map(jnp.copy, self.cache)
        logits, _, hidden = real(self, tokens)
        self.cache = before
        return logits, before, hidden
    monkeypatch.setattr(DecodeEngine, "decode_slots", decode_slots)


def _half_batch(monkeypatch):
    """Half of the batch left out: the second half of the rows gets the
    first half's logits."""
    from repro.serving.engine import DecodeEngine
    real = DecodeEngine.decode_slots

    def decode_slots(self, tokens):
        logits, cache, hidden = real(self, tokens)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:h]]), cache, hidden
    monkeypatch.setattr(DecodeEngine, "decode_slots", decode_slots)


@pytest.mark.parametrize("fault", [_alter_tokens, _stale_state, _half_batch],
                         ids=["token_altered", "state_unchanged",
                              "half_batch"])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tiny_root("tiny_dense", "tiny.closed"))
    assert not res["correct"], res["checks"]


def test_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stablelm_3b.spec.longgen", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, cwd=REPO,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "stablelm_3b.spec.longgen", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
