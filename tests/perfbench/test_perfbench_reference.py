"""The comparison that decides ``correct``, shown to pass and shown to fail,
at a small size on the CPU.

* the system against ``reference/postnorm_transformer.py``: the encoder
  (three optimizer steps) and the causal model (prefill then decode through
  the engine against one full forward) come out correct;
* the CONTROL, the reference with the operands of every matrix product
  rounded through float8_e4m3fn, comes out NOT correct under the same limits;
* with the timed path broken underneath (an optimizer step that returns its
  state unchanged; a token altered where it is produced) the rest of a run
  comes out ``correct: false``.

The limits of the tiny cells (``pb_tiny.TRAIN_LIMITS``/``SERVE_LIMITS``) were
set like the chip's, from the readings given beside them: above the largest
value sound runs gave on this CPU path and below the smallest the control gave.
"""

import numpy as np
import pytest

import pb_control
import pb_tiny


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return pb_tiny.tiny_tree(tmp_path_factory.mktemp("pbref"))


def _cell(tree, name):
    from perfbench.harness import cells

    return cells.load(tree, name)


@pytest.mark.parametrize("workload", ["tiny-enc.train", "tiny-lm.serve"])
@pytest.mark.parametrize("seed", [5, 2**31 + 77])
def test_the_system_agrees_with_the_reference(tree, workload, seed, capsys):
    result = pb_tiny.run(tree, workload, seed=seed, seconds=0.6)
    assert result["correct"] is True, capsys.readouterr().out
    for name, value, limit in result["compared"]:
        assert value <= limit, name


@pytest.mark.parametrize("workload", ["tiny-enc.train", "tiny-lm.serve"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_comes_out_not_correct(tree, workload, seed):
    numbers = pb_control.control_numbers(_cell(tree, workload), seed)
    assert any(not value <= limit for _, value, limit in numbers), numbers


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tree, monkeypatch, capsys):
    from flexflow_tpu import optimizers

    monkeypatch.setattr(optimizers.AdamOptimizer, "update",
                        lambda self, params, grads, state: (params, state))
    result = pb_tiny.run(tree, "tiny-enc.train", seed=9, seconds=0.4)
    assert result["correct"] is False
    failed = {n for n, v, limit in result["compared"] if not v <= limit}
    assert "delta_norm_worst_leaf" in failed, capsys.readouterr().out


def test_part_of_the_batch_left_out_is_not_correct(tree, monkeypatch):
    from flexflow_tpu.model import FFModel

    inner = FFModel.train_batch

    def half(self, x, y):
        # the second half of the rows is replaced by the first half
        h = len(x) // 2
        return inner(self, np.concatenate([x[:h], x[:h]]),
                     np.concatenate([y[:h], y[:h]]))

    monkeypatch.setattr(FFModel, "train_batch", half)
    result = pb_tiny.run(tree, "tiny-enc.train", seed=9, seconds=0.4)
    assert result["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tree, monkeypatch, capsys):
    from flexflow_tpu.serving.generation.engine import GenerationStream

    inner = GenerationStream._emit

    def altered(self, tok):
        inner(self, (tok + 1) % 211 if len(self._tokens) % 3 == 2 else tok)

    monkeypatch.setattr(GenerationStream, "_emit", altered)
    result = pb_tiny.run(tree, "tiny-lm.serve", seed=9, seconds=0.6)
    assert result["correct"] is False, capsys.readouterr().out


def test_the_reference_is_the_published_block_not_the_programs(tree):
    """Independent of the program: LayerNorm'ed outputs, a causal model whose
    logits at a position do not depend on later tokens, and an encoder whose
    do."""
    import jax.numpy as jnp

    cell = _cell(tree, "tiny-lm.serve")
    fam = cell.module("families", cell.config["family"])
    ref = cell.module("reference", fam.REFERENCE)
    sz = fam.sizes(cell.config)
    params = ref.init_params(sz, 4)
    tok = np.random.default_rng(0).integers(1, sz["vocab"], (1, 24))
    other = tok.copy()
    other[0, 12:] = (other[0, 12:] + 5) % sz["vocab"]
    a = ref.lm_logits(params, jnp.asarray(tok), sz)
    b = ref.lm_logits(params, jnp.asarray(other), sz)
    assert float(jnp.max(jnp.abs(a[0, :12] - b[0, :12]))) == 0.0
    assert float(jnp.max(jnp.abs(a[0, 12:] - b[0, 12:]))) > 0.0
    h = ref.hidden(params, jnp.asarray(tok), sz)
    np.testing.assert_allclose(np.asarray(h).mean(-1), np.asarray(
        params["ln2_b"][-1]).mean(), atol=1e-2)
    enc = dict(sz, causal=False)
    c = ref.hidden(params, jnp.asarray(tok), enc)
    d = ref.hidden(params, jnp.asarray(other), enc)
    assert float(jnp.max(jnp.abs(c[0, :12] - d[0, :12]))) > 0.0
