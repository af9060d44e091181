"""Prints, as one JSON object, the SHA-256 of the lowered text (kernels
interpreted) of the attention calls that share
``ops/pallas/ragged_paged_attention.py`` with no selection in play: the
K/V call, the K/V call under a window over a folded cache, the latent call
(Kimi's) and the latent call under a window in head groups (dots3's
sliding layers). ``--with-sparse`` imports
``ops/pallas/sparse_latent_attention.py`` (and the model that calls it)
first; without it the script fails if anything pulled that module in.
Run by ``test_sparse_latent_separation.py`` in processes of its own, and
by hand against a checkout of another commit (``PYTHONPATH=<checkout>``).
"""
import hashlib
import json
import sys

SPARSE = "paddle_tpu.ops.pallas.sparse_latent_attention"


def main():
    if "--with-sparse" in sys.argv[1:]:
        import importlib

        importlib.import_module(SPARSE)
        importlib.import_module("paddle_tpu.models.dots3")
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.ragged_paged_attention import (
        ragged_paged_attention,
    )

    f32, i32 = jnp.float32, jnp.int32
    t, s, mb, nb, bs = 32, 4, 12, 48, 4
    sds = jax.ShapeDtypeStruct
    stream = (sds((s, mb), i32), sds((s + 1,), i32), sds((s,), i32),
              sds((), i32))

    def latent(**more):
        def call(q, new, cache, *rest):
            return ragged_paged_attention(
                q, new, None, cache, None, *rest, impl="interpret",
                v_lanes=128, scale=0.1, **more)[:2]
        return call, (sds((t, 4, 256), f32), sds((t, 256), f32),
                      sds((nb, bs, 256), f32))

    def kv(cache_shape, **more):
        def call(q, k, v, kc, vc, *rest):
            return ragged_paged_attention(q, k, v, kc, vc, *rest,
                                          impl="interpret", **more)
        return call, (sds((t, 4, 128), f32), sds((t, 2, 128), f32),
                      sds((t, 2, 128), f32), sds(cache_shape, f32),
                      sds(cache_shape, f32))

    calls = {"kv": kv((nb, bs, 2, 128)),
             "kv_window_folded": kv((nb, bs, 256), window=6),
             "latent": latent(),
             "latent_window_head_groups": latent(window=6, head_block=2)}
    out = {}
    for name, (call, shapes) in calls.items():
        text = jax.jit(call).lower(*shapes, *stream).as_text()
        assert "ragged" in text, name
        out[name] = hashlib.sha256(text.encode()).hexdigest()
    if "--with-sparse" not in sys.argv[1:]:
        assert SPARSE not in sys.modules, (
            "the shared kernel's module pulled the selected call in")
    print(json.dumps(out))


if __name__ == "__main__":
    main()
