"""A configuration's own module (``chipbench/configs/<config>.py``): what
it defines replaces the default, what it leaves out stays the default,
and a run is checked and counted through it."""
import argparse
import dataclasses

import numpy as np
import pytest

import check
import counts
import run
import trace_reduce

SEED = 2**33 + 5

# check.gaps on the pairs of ``_pairs`` at SEED, read on the commit
# before configurations could bring a module: the default check has to
# give these same floats, bit for bit
PINNED = {
    "tiny_dense": {"max_logit_gap": 3.8222620487213135,
                   "mean_logit_gap": 2.647603750228882, "positions": 24,
                   "control": {"max_logit_gap": 0.6725648641586304,
                               "mean_logit_gap": 0.05364220216870308}},
    "tiny_moe": {"max_logit_gap": 0.9063323736190796,
                 "mean_logit_gap": 0.5488552451133728, "positions": 24,
                 "control": {"max_logit_gap": 0.1561804711818695,
                             "mean_logit_gap": 0.020523039624094963}},
}


def _pairs(vocab):
    rng = np.random.default_rng(11)
    return [(rng.integers(0, vocab, p), rng.integers(0, vocab, t))
            for p, t in ((9, 7), (23, 12), (14, 5))]


def _run(root, trace=0, on_run=None):
    args = argparse.Namespace(workload="tiny.cell", seed=SEED, seconds=2.0,
                              trace=trace)
    return run.run_cell(args, root=root, require_tpu=False, on_run=on_run)


def _module(root):
    return root / "chipbench" / "configs" / "tiny_hooked.py"


@pytest.mark.parametrize("name", ["tiny_dense", "tiny_moe"])
def test_without_a_module_the_defaults_give_the_pinned_gaps(tiny_root,
                                                            name):
    root = tiny_root(name, "tiny.closed")
    model = run.model_of(run.load_cell("tiny.cell", root))
    assert model == run.Model(run.arch_of, run.program_config, check.gaps,
                              counts.model_flops)
    arch = model.arch_of(run.load_cell("tiny.cell", root)["config"])
    finished = [(p, t, None) for p, t in _pairs(arch["vocab_size"])]
    assert model.gaps(arch, SEED, finished, control=True) == PINNED[name]


def test_a_module_replaces_what_it_defines(tiny_root):
    root = tiny_root("tiny_hooked", "tiny.closed")
    model = run.model_of(run.load_cell("tiny.cell", root))
    assert model.gaps.__module__ == "chipbench_config_tiny_hooked"
    assert (model.arch_of, model.program_config, model.model_flops) == (
        run.arch_of, run.program_config, counts.model_flops)
    _module(root).write_text(
        "def arch_of(config): return 'a'\n"
        "def program_config(config): return 'p'\n"
        "def gaps(arch, seed, finished, control=False): return 'g'\n"
        "def model_flops(arch, contexts): return 'f'\n")
    model = run.model_of(run.load_cell("tiny.cell", root))
    assert (model.arch_of(0), model.program_config(0), model.gaps(0, 0, 0),
            model.model_flops(0, 0)) == ("a", "p", "g", "f")


def test_a_module_checks_a_sound_run(tiny_root):
    """The module's check, rebuilt from the reference's blocks, passes a
    sound run and reads the default's gaps on the same sample."""
    root = tiny_root("tiny_hooked", "tiny.closed")
    seen = {}
    res = _run(root, on_run=lambda r: seen.setdefault("run", r))
    assert res["correct"], res["checks"]
    cell = run.load_cell("tiny.cell", root)
    done = run.finished_requests(seen["run"], seen["run"].w1)
    chosen = [done[i] for i in check.sample(
        done, SEED, cell["mix"]["check_requests"])]
    want = check.gaps(run.arch_of(cell["config"]), SEED, chosen)
    assert res["checks"]["max_logit_gap"]["value"] == want["max_logit_gap"]


def test_a_module_decides_correct(tiny_root):
    """The same sound run, under the module with its reference's head
    negated, is not correct: the module, not ``reference.py`` alone,
    decides."""
    root = tiny_root("tiny_hooked", "tiny.closed")
    with _module(root).open("a") as f:
        f.write("\n\ndef head_weight(arch, seed):\n"
                "    return -reference.head_weight(arch, seed)\n")
    res = _run(root)
    assert not res["correct"], res["checks"]


def test_the_readers_count_through_the_module(tiny_root, monkeypatch):
    """A module that counts twice the default's FLOPs doubles the mfu
    reading of the same traced run: ``Context`` carries the module."""
    root = tiny_root("tiny_hooked", "tiny.closed")
    with _module(root).open("a") as f:
        f.write("\n\nimport counts\n\n\ndef model_flops(arch, contexts):\n"
                "    return 2 * counts.model_flops(arch, contexts)\n")
    # the CPU's trace has no TPU plane: a window half busy stands in
    monkeypatch.setattr(trace_reduce, "reduce_file", lambda path:
                        trace_reduce.Reduced(2.0, 1.0, {}, {}, {}, 1))
    seen, load = {}, run.load_reader

    def reader(name):
        def read(ctx):
            seen[name] = ctx
            return load(name)(ctx)
        return read
    monkeypatch.setattr(run, "load_reader", reader)
    res = _run(root, trace=1)
    ctx = seen["step.decode_mfu"]
    default = dataclasses.replace(ctx, model=dataclasses.replace(
        ctx.model, model_flops=counts.model_flops))
    want = load("step.decode_mfu")(default)
    assert want > 0
    assert res["metrics"]["step.decode_mfu"]["value"] == pytest.approx(
        2 * want, rel=1e-12)
