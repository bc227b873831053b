"""A block-diffusion configuration (SDAR) through the whole run at toy
size on the CPU: the configuration's own check passes a sound run and
reads a corrupted refinement state as not correct; the new per-layer
reader counts each finished request's decode forwards."""
import argparse
import shutil
from types import SimpleNamespace

import pytest

import run
from conftest import BENCH

SEED = 2**33 + 17


@pytest.fixture
def sdar_root(tiny_root):
    """The toy cell with the real configuration module."""
    root = tiny_root("tiny_sdar", "tiny.diffusion")
    shutil.copy(BENCH / "configs" / "sdar_30b_a3b.py",
                root / "chipbench" / "configs" / "tiny_sdar.py")
    return root


def _run(root, seed=SEED):
    args = argparse.Namespace(workload="tiny.cell", seed=seed, seconds=2.0,
                              trace=0)
    return run.run_cell(args, root=root, require_tpu=False)


def test_sound_block_diffusion_run_is_correct(sdar_root):
    res = _run(sdar_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert res["metrics"]["output_tok_per_s"]["value"] > 0


def _masks_read_as_token_zero(monkeypatch):
    """Refinement forwards see token 0 wherever the state holds MASK: the
    program refines a state other than the one it records."""
    from repro.serving.diffusion import BlockDiffusionSlotAdapter
    from repro.serving.scheduler import ServingLoop
    real = ServingLoop.shared_forward

    def shared_forward(self, tokens, budget):
        if isinstance(self.adapter, BlockDiffusionSlotAdapter):
            tokens = tokens.copy()
            tokens[tokens == self.adapter.mask_id] = 0
        return real(self, tokens, budget)
    monkeypatch.setattr(ServingLoop, "shared_forward", shared_forward)


def _steps_recorded_first(monkeypatch):
    """Every position is recorded as fixed at its block's first step: the
    state the check rebuilds for a later pick lacks the positions fixed
    before it."""
    from repro.serving.diffusion import BlockDiffusionSlotAdapter
    real = BlockDiffusionSlotAdapter.run_step

    def run_step(self, slots, width, budget):
        marks = {s: len(self.loop.active[s].fixed_at) for s in slots}
        real(self, slots, width, budget)
        for s, m in marks.items():
            req = self.loop.active[s]
            req.fixed_at[m:] = [0] * (len(req.fixed_at) - m)
    monkeypatch.setattr(BlockDiffusionSlotAdapter, "run_step", run_step)


@pytest.mark.parametrize("fault", [_masks_read_as_token_zero,
                                   _steps_recorded_first],
                         ids=["mask_read_as_token", "steps_recorded_first"])
def test_corrupted_refinement_state_is_not_correct(sdar_root, monkeypatch,
                                                   fault):
    fault(monkeypatch)
    res = _run(sdar_root)
    assert not res["correct"], res["checks"]


def test_tokens_per_row_forward_reads_the_request_counter():
    read = run.load_reader("adapter.tokens_per_row_forward")

    def rec(n, last, forwards):
        return SimpleNamespace(n=n, max_tokens=n, last=last,
                               req=SimpleNamespace(forwards=forwards))
    recs = {0: rec(8, 1.5, 10), 1: rec(4, 2.5, 5), 2: rec(9, 0.5, 99),
            3: rec(3, 1.0, None)}
    ctx = SimpleNamespace(run=SimpleNamespace(w0=1.0, w1=3.0, recs=recs))
    # request 2 finished before the window; request 3 has no counter
    assert read(ctx) == pytest.approx(12 / 15)
    ctx.run.recs = {3: recs[3]}
    assert read(ctx) is None
