"""The float32 reference against the program's own forward, at toy
widths on the CPU, with the same seeded weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference
import run
import weights

run.import_program()


@pytest.mark.parametrize("name", ["tiny_dense", "tiny_moe"])
def test_reference_matches_program_in_float32(name):
    from repro.models.transformer import forward
    from repro.models import init_model
    config = run._json(run.HERE / "testdata" / f"{name}.json")
    arch = run.arch_of(config)
    cfg = run.program_config(config)
    seed = 2**35 + 3
    shapes = jax.eval_shape(lambda k: init_model(k, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          weights.build(shapes, seed))
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, arch["vocab_size"], n) for n in (37, 20)]
    hidden = reference.final_hidden(arch, seed, seqs, rows=2, pad_to=64)
    w = reference.head_weight(arch, seed)
    for s, h in zip(seqs, hidden):
        with jax.default_matmul_precision("highest"):
            logits = forward(params, cfg, {"tokens": jnp.asarray(s)[None]},
                             mode="train")[0][0]
        ref = np.asarray(jnp.matmul(h, w, precision="highest"))
        np.testing.assert_allclose(ref, np.asarray(logits), rtol=0,
                                   atol=2e-4 * np.abs(ref).max())


def test_fp8_control_departs_from_float32():
    config = run._json(run.HERE / "testdata" / "tiny_dense.json")
    arch = run.arch_of(config)
    seqs = [np.arange(30) % arch["vocab_size"]]
    hi = reference.final_hidden(arch, 1, seqs, "f32", pad_to=32)[0]
    lo = reference.final_hidden(arch, 1, seqs, "fp8", pad_to=32)[0]
    assert np.abs(hi - lo).max() > 1e-2 * np.abs(hi).max()


def test_layer_draws_match_the_stacked_build():
    key = weights.base_key(2**40 + 1)
    stacked = weights.stacked_leaf(key, "segments/0/attn/wq", (3, 8, 4),
                                   jnp.bfloat16)
    one = weights.layer_leaf(key, "segments/0/attn/wq", 2, (8, 4),
                             jnp.bfloat16)
    assert bool((stacked[2] == one).all())
