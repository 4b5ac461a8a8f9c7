"""The decoders' log-likelihood head as ONE Pallas call (`nn/loglik.py`:
`loglik_head`, `head_tiles`, `token_logprobs`): the chip's kernel runs here
interpreted, at tiny tiles, against XLA's chunked path
(`chunked_logprobs`) on the same bfloat16 operands.

The limit, with its reason: both paths take float32 products of the same
operands and sum a row's exponentials in float32, the kernel a lane at a
time over the vocabulary's blocks and XLA in its reduction's order, so a
log-probability of size 10 moves by float32's last digits: 1e-5."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.nn import loglik, models
from mmlspark_tpu.observability.metrics import get_registry

CLOSE = 1e-5

CASES = {
    # tokens, d, vocabulary, tied, multiplier, (tokens, columns) of a tile
    "tied": (128, 128, 512, True, 1.0, (64, 128)),
    "untied": (128, 128, 512, False, 1.0, (64, 128)),
    "a_multiplier": (128, 128, 512, False, 0.0078125, (64, 128)),
    "a_multiplier_tied": (128, 128, 480, True, 0.25, (64, 128)),
    # SmallThinker's own vocabulary, 74.1875 blocks of 512
    "ragged_37984": (48, 128, 37984, False, 1.0, (16, 512)),
    # 200064 = 390.75 blocks of 512: the same remainder of a block of 128
    "ragged_like_200064": (128, 128, 6 * 128 + 96, True, 1.0, (64, 128)),
    "tokens_no_multiple_of_the_tile": (100, 128, 480, True, 1.0, (64, 128)),
    "fewer_tokens_than_a_tile": (40, 256, 640, False, 1.0, (64, 256)),
    # a tile of 1024 tokens is folded in two row parts
    "two_row_parts": (1100, 128, 480, True, 1.0, (1024, 128)),
    "one_block_wider_than_the_lanes": (64, 128, 1200, False, 1.0, (32, 384)),
}


def _operands(n, d, vocab, tied, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    h = jax.random.normal(keys[0], (n, d), jnp.float32).astype(jnp.bfloat16)
    # logits of a few units: log-probabilities of size 10
    head = (jax.random.normal(keys[1], (vocab, d) if tied else (d, vocab))
            * 3 * d ** -0.5).astype(jnp.bfloat16)
    target = jax.random.randint(keys[2], (n,), 0, vocab)
    # the first block, a middle one and the ragged last one, its last column
    target = target.at[0].set(0).at[1].set(vocab // 2).at[2].set(vocab - 1)
    return h, target, head


@pytest.mark.parametrize("case", [*CASES, "every_target_counted_once",
                                  "the_gradient_is_the_xla_paths"])
def test_the_kernel_gives_the_chunked_paths_log_probabilities(case):
    if case == "every_target_counted_once":
        # every logit is 64, so a token's log-probability is -log V where
        # its target's logit is added exactly once, whatever block holds it
        n, d, vocab, tiles = 96, 128, 480, (32, 128)
        out = loglik.loglik_head(
            jnp.ones((n, d), jnp.bfloat16), jnp.arange(n) * 5,
            jnp.full((vocab, d), 0.5, jnp.bfloat16), tied=True, tiles=tiles,
            interpret=True)
        np.testing.assert_allclose(out, -np.log(vocab), atol=CLOSE)
        return
    if case == "the_gradient_is_the_xla_paths":
        h, target, head = _operands(100, 128, 480, True)
        weights = jnp.linspace(0.5, 1.5, 100)

        def kernel(h, head):
            return (weights * loglik.loglik_head(
                h, target, head, tied=True, tiles=(64, 128),
                interpret=True)).sum()

        def xla(h, head):
            return (weights * loglik.chunked_logprobs(
                h, target, head.T, chunk=64)).sum()

        for ours, theirs in zip(jax.grad(kernel, (0, 1))(h, head),
                                jax.grad(xla, (0, 1))(h, head)):
            assert ours.dtype == theirs.dtype == jnp.bfloat16
            np.testing.assert_array_equal(ours, theirs)
        return
    n, d, vocab, tied, multiplier, tiles = CASES[case]
    h, target, head = _operands(n, d, vocab, tied)
    ours = loglik.loglik_head(h, target, head, tied=tied, tiles=tiles,
                              multiplier=multiplier, interpret=True)
    theirs = loglik.chunked_logprobs(h, target, head.T if tied else head,
                                     multiplier=multiplier, chunk=64)
    assert ours.shape == (n,) and ours.dtype == jnp.float32
    if multiplier == 1.0:
        assert float(-theirs.min()) > 10
    np.testing.assert_allclose(ours, theirs, atol=CLOSE, rtol=0)


@pytest.mark.parametrize("tokens,d,vocab,chunk,tiles", [
    # the five cells' heads at their long batches and two short ones
    (32768, 2560, 200064, 1024, (1024, 384)),
    (32768, 2048, 65536, 1024, (1024, 512)),
    (32768, 2048, 40960, 1024, (1024, 512)),
    (32768, 2560, 37984, 1024, (1024, 384)),
    (32768, 5120, 32640, 1024, (1024, 384)),
    (2048, 2048, 65536, 1024, (1024, 512)),
    (4096, 2560, 200064, 1024, (1024, 384)),
    # fewer tokens than a chunk: the tokens, in whole registers' rows
    (100, 2048, 65536, 1024, (112, 1024)),
    # a width that is no whole lanes, EvaByte's 320 bytes a head
    (8192, 2000, 65536, 1024, None), (65536, 4096, 320, 1024, None),
    # a chunk that is no whole registers' rows: XLA's path takes any
    (32768, 2560, 200064, 100, None), (32768, 2048, 65536, 1000, None)])
def test_the_rule_counts_vmem_from_the_shapes(tokens, d, vocab, chunk, tiles):
    found = loglik.head_tiles(tokens, d, vocab, 2, chunk)
    assert found == tiles
    if tiles is None:
        return
    tm, tn = found
    assert tm % 16 == 0 and tn % 128 == 0 and tm <= chunk
    assert loglik._head_bytes(tm, tn, d, 2) <= loglik.STEP_VMEM
    # float32 operands are XLA's
    assert loglik.head_tiles(tokens, d, vocab, 4, chunk) is None


def _decoder(tied: bool, dtype):
    kind = "hybrid_moe_decoder" if tied else "mla_moe_decoder"
    return models.make_model(kind, d_model=128, vocab_size=1024, dtype=dtype)


@pytest.mark.parametrize("backend,dtype,kernel", [
    ("cpu", jnp.bfloat16, "xla"), ("tpu", jnp.bfloat16, "pallas"),
    # float32 operands stay XLA's wherever they run
    ("tpu", jnp.float32, "xla")])
@pytest.mark.parametrize("tied", [True, False])
def test_a_traced_decoder_counts_one_head_a_compiled_shape(
        monkeypatch, tied, backend, dtype, kernel):
    """The counter says what runs: counted where the head is traced, one a
    compiled shape, under the path the rule chose and the head's kind."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    module = _decoder(tied, dtype)
    assert bool(module.tie_embeddings) == tied
    variables = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))

    def count():
        total = get_registry().counter(
            "mmlspark_tpu_loglik_head_calls_total", "", labels=(
                "kernel", "head"))
        return {(k, t): total.labels(kernel=k, head=t).value
                for k in ("pallas", "xla") for t in ("tied", "untied")}

    before = count()
    for length in (64, 128):
        jax.eval_shape(module.apply, variables,
                       jax.ShapeDtypeStruct((2, length), jnp.int32))
    after = count()
    moved = {key: after[key] - before[key] for key in after
             if after[key] != before[key]}
    assert moved == {(kernel, "tied" if tied else "untied"): 2.0}
