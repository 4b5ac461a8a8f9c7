"""Two decisions under `mmlspark_tpu/nn/` have one owner each, and the tree
says so (in the manner of `tests/test_docs_paths.py`): how attention runs
(the tier, the lane rule, the rotary form: `nn/attention/`) and what a model
family reports about a call (the family: `call_span_arguments`; the runner
knows no family by name)."""

import pathlib
import re

import jax
import numpy as np

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
    assert set(plain.args) == {"rows", "batch_size"}
