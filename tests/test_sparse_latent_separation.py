"""The selected latent call lives in a file of its own
(``ops/pallas/sparse_latent_attention.py``) so that the calls every other
cell runs (K/V, K/V under a window, Kimi's latent call, the windowed
latent call) stay what they were. Held here, not by a builder's diff: each
of them lowers to the same text in a process that never imports that
module and in one that does (kernels interpreted; lowered text carries no
line numbers)."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CALLS = ["kv", "kv_window_folded", "latent", "latent_window_head_groups"]


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


def test_the_shared_kernel_takes_no_selection():
    """``selected=`` is gone from the paged call and its kernel: a
    selection is the other file's business (the ``jnp`` reference keeps
    the mask: it is that file's oracle and CPU route)."""
    import inspect

    from paddle_tpu.ops.pallas import ragged_paged_attention as paged

    for fn in (paged.ragged_paged_attention, paged._ragged_kernel,
               paged._latent_attention,
               paged._ragged_attend_pallas.__wrapped__):
        assert "selected" not in inspect.signature(fn).parameters
    assert "selected" in inspect.signature(
        paged._ragged_attend_ref).parameters
    assert "sel_ref" not in inspect.getsource(paged)
    assert not hasattr(paged, "_ragged_kernel_selected")
