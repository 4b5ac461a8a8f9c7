"""Test session bootstrap.

Role of the reference's TestBase + SparkSessionFactory (`core/test/base/
TestBase.scala:42-206`): one shared local session for all suites. Here the
"local[*] session" analogue is the CPU XLA backend with 8 virtual devices, so
multi-chip sharding logic (mesh + collectives) runs inside one process —
matching how the reference simulates multi-node with partitions-in-one-JVM.

Must set env BEFORE jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run on the CPU backend
# No persistent compile cache for the test session (workers inherit this).
# The package would place one in <checkout>/.jax_cache. Tier-1 runs on a
# fresh checkout, where a cache is cold: it can only add work (hashing,
# serializing, writing) to a suite that ends within a minute of its 870 s
# limit. Readings on this box (PR 21): cache off 820 s and 867 s, warm
# 792 s, cold killed at the limit at 96%.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from mmlspark_tpu.parallel import make_mesh

    return make_mesh(n_data=8)


@pytest.fixture()
def rng():
    return np.random.default_rng(42)


def subprocess_env():
    """Env for test subprocesses: the repo root importable ahead of
    whatever PYTHONPATH already holds; JAX_PLATFORMS=cpu and the eight
    forced host devices ride along from os.environ."""
    import pathlib

    repo = str(pathlib.Path(__file__).parent.parent)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [repo] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    return env


def kernel_equations(jaxpr) -> list:
    """The equations of every Pallas kernel's body under `jaxpr`, however
    deep, a count a kernel: what a start lowers."""
    def subjaxprs(eqn):
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield inner

    def deep(inner):
        return sum(1 + sum(deep(sub) for sub in subjaxprs(eqn))
                   for eqn in inner.eqns)

    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(deep(eqn.params["jaxpr"]))
        else:
            for sub in subjaxprs(eqn):
                found += kernel_equations(sub)
    return found


def at_device_shapes(pipeline_at, table, bs, shards=1):
    """`table` scored by `pipeline_at(rows)`, the same stages built to run
    `rows` at a time on one device, one batch of a fused pipeline (`bs`
    rows) after the other, `rows` being what ONE DEVICE of a `shards`-way
    data mesh holds of that batch once it is padded to its rung. The same
    program at the same shape answers bit for bit; at another shape it
    need not (XLA:CPU sums a one-row product in another order than a
    larger one's, and where the sum cancels the outputs drift by tens of
    units in the last place)."""
    from mmlspark_tpu.core.dataplane import ShapeBucketer

    bucketer = ShapeBucketer(bs, shards=shards)
    out = None
    for lo in range(0, table.num_rows, bs):
        m = min(bs, table.num_rows - lo)
        rows = bucketer.bucket_for(m) // shards
        # whole batches of `rows`: the last row again, as the bucketer pads
        idx = np.minimum(np.arange(lo, lo + -(-m // rows) * rows), lo + m - 1)
        part = pipeline_at(rows).transform(table.gather(idx)).take(m)
        out = part if out is None else out.concat(part)
    return out
