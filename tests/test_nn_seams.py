"""Two decisions under `mmlspark_tpu/nn/` have one owner each, and the tree
says so (in the manner of `tests/test_docs_paths.py`): how attention runs
(the tier, the lane rule, the rotary form: `nn/attention/`) and what a model
family reports about a call (the family: `call_span_arguments`; the runner
knows no family by name)."""

import pathlib
import re

import jax
import numpy as np
import pytest

from mmlspark_tpu.core.schema import Table
from mmlspark_tpu.nn import attention, models
from mmlspark_tpu.nn.models import MLP, ModelBundle
from mmlspark_tpu.nn.runner import DeepModelTransformer
from mmlspark_tpu.observability.tracing import get_tracer

NN = pathlib.Path(__file__).parent.parent / "mmlspark_tpu" / "nn"


def test_how_attention_runs_and_what_a_call_reports_have_one_owner():
    # the backend is asked in ONE function of the package ...
    asking = []
    for path in sorted((NN / "attention").glob("*.py")):
        source = path.read_text()
        for found in re.finditer(r"default_backend", source):
            before = source[:found.start()]
            asking.append((path.name, re.findall(
                r"^def (\w+)", before, re.MULTILINE)[-1]))
    assert asking == [("layout.py", "tier")]
    # ... and nowhere in the models, which hold no lane rule and no rotary
    # arithmetic either
    family = (NN / "models.py").read_text()
    for gone in ("default_backend", "% 128", "_tier", "cos", "sin("):
        assert gone not in family, gone
    # the data plane knows no model family
    runner = (NN / "runner.py").read_text()
    for word in ("moe", "expert", "loop_", "window", "parallel.moe"):
        assert word not in runner.lower(), word


def test_the_package_exports_what_the_module_did():
    assert attention.__all__ == [
        "dense_attention", "chunked_attention", "flash_attention",
        "flash_tiles", "causal_attention", "band_tiles", "band_tile_pairs",
        "latent_attention", "eva_summaries", "eva_attention",
        "eva_tile_pairs", "rotary_in_lanes", "rotary_lanes_whole",
        "HeadsDense", "HeadsOut", "SelfAttention"]
    # one core a file: every export has a home under the package
    homes = {getattr(attention, name).__module__ for name in
             attention.__all__} - {"mmlspark_tpu.parallel.ring_attention"}
    assert homes and all(
        home.startswith("mmlspark_tpu.nn.attention.") for home in homes)
    assert not (NN / "attention.py").exists()


class _Reporting(MLP):
    def call_span_arguments(self, counted, scored, row_shape):
        return {"marker": [dict(counted), list(scored), tuple(row_shape)]}


def _root_after_a_call(monkeypatch, family):
    monkeypatch.setitem(models.ARCHITECTURES, "stand_in",
                        lambda **kw: family(**kw))
    config = dict(features=(8,), num_outputs=2)
    variables = family(**config).init(jax.random.PRNGKey(0),
                                      np.zeros((1, 5), np.float32))
    bundle = ModelBundle(architecture="stand_in", config=config,
                         variables=variables, input_shape=(5,))
    stage = DeepModelTransformer(
        input_col="x", fetch_dict={"out": "logits"}, mini_batch_size=4,
        fused_dispatch=False).set_model(bundle)
    stage.transform(Table({"x": np.ones((6, 5), np.float32)}))
    return [s for s in get_tracer().spans()
            if s.name == "runner.transform"][-1]


def test_what_a_module_reports_lands_on_the_calls_root_span(monkeypatch):
    """The runner's one question to a module: given what you sowed for
    these batches, what goes on the call's span? 6 rows in batches of 4:
    the tail of 2 is scored as it is padded."""
    root = _root_after_a_call(monkeypatch, _Reporting)
    assert root.args["marker"] == [{}, [4, 2], (5,)]
    plain = _root_after_a_call(monkeypatch, MLP)
    assert set(plain.args) == {"rows", "batch_size", "row_shape"}


# What the six families that keep nothing were BEFORE the skeleton gained its
# seat for kept arrays and its second kind of norm (read on the parent tree
# of PR 49 and on the change, equal to the last digit): the leaves and the
# parameters of the default tree, the equations of the traced forward and the
# sum of the log-probabilities of 2 seeded rows of 24 tokens.
BEFORE_THE_SEAT = {
    "mla_moe_decoder": (28, 147336, 485, -275.55962586402893),
    "hybrid_moe_decoder": (23, 119848, 364, -1830.429235458374),
    "eva_decoder": (25, 266816, 348, -284.443870306015),
    "window_moe_decoder": (23, 156992, 452, -271.880491733551),
    "looped_decoder": (27, 115329, 397, -272.39950346946716),
    "ssm_hybrid_decoder": (37, 150360, 681, -285.7063875198364),
}


@pytest.mark.parametrize("family", sorted(BEFORE_THE_SEAT))
def test_a_family_that_keeps_nothing_is_what_it_was(family):
    """The seat for arrays a layer keeps and the norm's kind are a family's
    own to state: the six that state neither trace to the equations they
    traced to, over the tree they had, and score what they scored; and the
    runner learned no word of the family that does."""
    leaves, params, equations, total = BEFORE_THE_SEAT[family]
    module = models.make_model(family)
    assert (module.layers_share, module.norm_kind) == (False, "rms")
    ids = jax.numpy.asarray(np.random.default_rng(0).integers(
        0, 200, (2, 24)), jax.numpy.int32)
    variables = module.init(jax.random.PRNGKey(0), ids)
    tree = jax.tree.leaves(variables["params"])
    assert (len(tree), sum(a.size for a in tree)) == (leaves, params)
    traced = str(jax.make_jaxpr(lambda v, x: module.apply(
        v, x, capture_intermediates=True, mutable=["intermediates"]))(
            variables, ids))
    assert traced.count(" = ") == equations
    assert float(np.asarray(module.apply(variables, ids), np.float64).sum()
                 ) == pytest.approx(total, rel=1e-5)
    runner = (NN / "runner.py").read_text().lower()
    for word in ("sel_scan", "shared_reads", "kept", "hybrid", "gmu"):
        assert word not in runner, word
