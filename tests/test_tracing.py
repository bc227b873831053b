"""The serving path's own trace: host spans on the profiler's clock and
named scopes in the device programs.

A tiny paged ``ServingLoop`` (kernel path, interpreted on the CPU) runs
under ``jax.profiler`` for a dense and an MoE config; the tests read the
recorded ``.xplane.pb`` back with ``jax.profiler.ProfileData``.
"""
from __future__ import annotations

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import init_model
from repro.serving import DecodeEngine, PagedKVConfig, ServingLoop
from repro.serving.engine import _decode_paged_fn

ARCHS = ["stablelm_3b", "granite_moe_3b_a800m"]

#: every span the serving path records, and the span it runs inside
PARENT = {
    "repro.scheduler.admit": None,
    "repro.engine.prefill": "repro.scheduler.admit",
    "repro.scheduler.first_token": "repro.scheduler.admit",
    "repro.scheduler.step": None,
    "repro.adapter.draft": "repro.scheduler.step",
    "repro.scheduler.log_forward": "repro.scheduler.step",
    "repro.engine.decode": "repro.scheduler.step",
    "repro.adapter.readback": "repro.scheduler.step",
    "repro.adapter.accept": "repro.scheduler.step",
    "repro.engine.commit": "repro.scheduler.step",
    "repro.scheduler.retire": "repro.scheduler.step",
}


@pytest.fixture(scope="module", params=ARCHS)
def served(request, tmp_path_factory):
    """(engine, loop, requests, spans) of three speculative requests on
    two slots served under the profiler; spans are (name, start_ns,
    end_ns, args) in start order."""
    cfg = get_config(request.param, reduced=True)
    params = init_model(jax.random.PRNGKey(0), cfg)
    engine = DecodeEngine(cfg, params, batch=2, max_len=256,
                          use_kernel=True,
                          paged=PagedKVConfig(block_size=128))
    loop = ServingLoop(engine, mode="speculative")
    rng = np.random.default_rng(0)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        reqs = [loop.submit(rng.integers(0, cfg.vocab_size, p), 6)
                for p in (10, 5, 12)]
        loop.run()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    spans = sorted(
        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
         dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("repro."))
    return engine, loop, reqs, spans


def test_every_span_appears_nested(served):
    _, loop, reqs, spans = served
    names = {n for n, *_ in spans}
    assert names == set(PARENT)
    for name, s, e, _ in spans:
        parent = PARENT[name]
        inside = [p for p in spans if p[0] == parent and p[1] <= s
                  and e <= p[2]]
        assert (parent is None) or inside, (name, s, e)
    steps = [a for n, _, _, a in spans if n == "repro.scheduler.step"]
    assert len(steps) == len(loop.step_log)
    assert all(a["active"] >= 1 and a["width"] >= 1 for a in steps)
    # spans of one request share its rid: admitted once, retired once
    def rids(name):
        return sorted(int(r) for n, _, _, a in spans if n == name
                      and "rids" in a for r in str(a["rids"]).split())
    assert rids("repro.scheduler.admit") == [r.rid for r in reqs]
    assert rids("repro.scheduler.retire") == [r.rid for r in reqs]


def test_prefill_span_arguments_are_the_prefill_log(served):
    engine, _, _, spans = served
    args = [a for n, _, _, a in spans if n == "repro.engine.prefill"]
    assert args == [{k: e[k] for k in ("computed_tokens", "padded_tokens",
                                       "bucket")}
                    for e in engine.prefill_log]
    assert all(e["padded_tokens"] == engine.batch * e["bucket"]
               for e in engine.prefill_log)


def test_decode_program_carries_scopes_and_kernel_name(served):
    engine, _, _, _ = served
    hlo = _decode_paged_fn.lower(
        engine.params, engine.cfg, jnp.zeros((engine.batch, 3), jnp.int32),
        engine.cache, engine.slot_lens, engine._device_tables(),
        use_kernel=True).compile().as_text()
    ops = set(re.findall(r'op_name="([^"]*)"', hlo))
    prog = "jit(_decode_paged_fn)/layers/"
    assert any(o.startswith(prog) and "/attention/" in o for o in ops)
    assert any(o.startswith(prog) and "/ffn/" in o for o in ops)
    kernel = "/attention/jit(decode_attention_paged)/decode_attention_paged/"
    assert any(o.startswith(prog) and kernel in o for o in ops)


def test_requests_stamp_submit_before_admit(served):
    _, _, reqs, _ = served
    for r in reqs:
        assert r.t_admit is not None and r.t_submit <= r.t_admit
    # the third request waited for a slot
    assert reqs[2].t_admit > reqs[1].t_admit
