"""The latent calls have a kernel and a file of their own
(``ops/pallas/sparse_latent_attention.py``: Kimi's call, the windowed
call and the selected call, one body) so that the K/V calls every other
cell runs stay what they were: a K/V call never pulls that module in, and
every call lowers to the same text in a process that imported it first
and in one that did not (kernels interpreted; lowered text carries no line
numbers). The same script, run against a checkout of a PR's parent, says
which calls that PR left as they were."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CALLS = ["kv", "kv_window_folded", "latent", "latent_window", "selected"]


def _hashes(*flags):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.dirname(HERE), os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "lowered_attention_calls.py"),
         *flags], env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def lowered():
    return _hashes(), _hashes("--with-sparse")


@pytest.mark.parametrize("call", CALLS)
def test_shared_calls_lower_the_same_beside_the_selected_call(lowered,
                                                              call):
    without, with_sparse = lowered
    assert sorted(without) == sorted(with_sparse) == sorted(CALLS)
    assert without[call] == with_sparse[call]


def test_the_kv_kernel_has_no_latent_mode():
    """``_ragged_kernel`` is the K/V kernel it was before PR 33: no
    ``v_lanes``, head groups or selection in it or in its jitted caller
    (the ``jnp`` reference keeps all three: it is the latent kernel's
    oracle and CPU route); the paged call hands a latent call on."""
    import inspect

    from paddle_tpu.ops.pallas import ragged_paged_attention as paged

    for fn in (paged._ragged_kernel,
               paged._ragged_attend_pallas.__wrapped__):
        for gone in ("selected", "v_lanes", "head_block"):
            assert gone not in inspect.signature(fn).parameters
    for kept in ("selected", "v_lanes", "window"):
        assert kept in inspect.signature(paged._ragged_attend_ref).parameters
    assert "selected" not in inspect.signature(
        paged.ragged_paged_attention).parameters
    assert "head_block" not in inspect.signature(
        paged.ragged_paged_attention).parameters
    source = inspect.getsource(paged._ragged_kernel)
    assert "latent" not in source and "sel_ref" not in source
