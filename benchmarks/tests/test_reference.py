"""The plain reference against the program at a toy size on the CPU: the
same comparison the chip run makes, and proof that it can fail."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import arith, model, reference

TOY = Path(__file__).resolve().parent / "data" / "toy" / "configs"


@pytest.fixture(scope="module", params=["toy-olmo", "toy-mistral"])
def toy(request):
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    c = json.loads((TOY / f"{request.param}.json").read_text())
    c["serving"] = {"weights": "float32", "compute": "float32"}
    cfg = model.transformer_config(c, "serving")
    params = model.init_weights_on_device(cfg, 2**31 + 5)
    return c, cfg, params, arith.Shapes.from_config(c)


def test_weights_have_the_programs_tree_and_depend_on_the_seed(toy):
    import jax
    from shallowspeed_tpu.models import transformer as T

    c, cfg, params, _ = toy
    want = jax.tree_util.tree_map(lambda a: a.shape, T.init(cfg, 0))
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == want
    other = model.init_weights_on_device(cfg, 6)
    assert not np.allclose(params["tok_emb"], other["tok_emb"])
    w = np.asarray(params["blocks"][0]["up"]["W"])
    assert w.std() == pytest.approx(cfg.d_model ** -0.5, rel=0.1)


def test_reference_logits_match_the_programs_forward(toy):
    from shallowspeed_tpu.models import transformer as T

    c, cfg, params, shapes = toy
    tokens = np.random.default_rng(0).integers(0, shapes.vocab, 96)
    hid, = reference.hidden_states(params, [tokens], shapes, c["program"],
                                   c["rope_theta"])
    ours = np.asarray(reference.head_logits(params, hid))
    theirs = np.asarray(T.forward(params, tokens[None], cfg))[0]
    assert np.abs(ours - theirs).max() < 2e-4


def test_engine_tokens_pass_and_a_wrong_token_fails(toy):
    from shallowspeed_tpu.serving.engine import ServingEngine

    c, cfg, params, shapes = toy
    eng = ServingEngine(params, cfg, n_blocks=32, block_size=16, max_slots=2,
                        prefill_chunk=32)
    prompt = np.random.default_rng(1).integers(0, shapes.vocab, 40)
    rid = eng.submit(prompt, 12)
    out = np.asarray(eng.run()[rid])
    args = (shapes, c["program"], c["rope_theta"])
    gaps = reference.chosen_logit_gaps(params, prompt, out, *args, last=8)
    assert gaps.shape == (8,) and gaps.max() < 1e-3
    wrong = out.copy()
    wrong[-3] = (wrong[-3] + 1) % shapes.vocab
    bad = reference.chosen_logit_gaps(params, prompt, wrong, *args, last=8)
    assert bad.max() > 0.15          # the driver's tolerance would refuse it


def test_reference_loss_matches_the_programs_loss(toy):
    from shallowspeed_tpu.models import transformer as T

    c, cfg, params, shapes = toy
    rows = np.random.default_rng(2).integers(0, shapes.vocab, (3, 65))
    ours = reference.batch_loss(params, rows[:, :-1], rows[:, 1:], shapes,
                                c["program"], c["rope_theta"])
    theirs = float(T.loss(params, rows[:, :-1], rows[:, 1:], cfg))
    assert ours == pytest.approx(theirs, abs=1e-4)
    shifted = reference.batch_loss(params, rows[:, :-1], rows[:, :-1], shapes,
                                   c["program"], c["rope_theta"])
    assert abs(shifted - theirs) > 0.02          # the driver's tolerance
