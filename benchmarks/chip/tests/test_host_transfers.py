"""The reader of the engine's transfer counter
(``host_transfers_per_step.decode``), on synthetic counters and on the
counters a tiny engine leaves on the CPU."""

from __future__ import annotations

import pytest

import run as bench

TRANSFERS = bench.load_reader("host_transfers_per_step.decode")
READS = bench.load_reader("host_reads_per_step.decode")


@pytest.fixture
def registry():
    from repro import obs

    obs.reset()
    obs.set_enabled(True)
    yield obs
    obs.reset()
    obs.set_enabled(None)


def test_host_transfers_per_step(registry):
    assert TRANSFERS({}) is None  # no counters: a program without them
    registry.inc("serving.host_transfers", phase="prefill")
    for _ in range(4):
        registry.inc("serving.decode_steps")
        registry.inc("serving.host_transfers", phase="decode")
    assert TRANSFERS({}) == 1.0


def test_the_engine_makes_one_transfer_per_decode_step(registry):
    """A tiny engine serving two rows makes one transfer a step and reads
    both rows' tokens in it."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.serving.engine import ServingEngine

    cfg = get_config("qwen3_4b").reduced()
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, max_batch=2, max_seq=32)
    prompts = list(np.random.default_rng(5).integers(
        0, cfg.vocab, (2, 8), dtype=np.int32))
    engine.run(engine.submit_many(prompts, max_new_tokens=4))
    assert TRANSFERS({}) == 1.0
    assert READS({}) == 2.0
