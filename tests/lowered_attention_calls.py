"""Prints, as one JSON object, the SHA-256 of the lowered text (kernels
interpreted) of the attention calls: the K/V call, the K/V call under a
window over a folded cache (both ``ops/pallas/ragged_paged_attention.py``'s
kernel), the latent call (Kimi's), the latent call under a window (dots3's
sliding layers) and the selected latent call at the fifth cell's own
shape (all three ``ops/pallas/sparse_latent_attention.py``'s kernel).
``--with-sparse`` imports that module (and the model that calls the
selected call) first; without it the script fails if a K/V call pulled
the module in. Run by ``test_sparse_latent_separation.py`` in processes
of its own, and by hand against a checkout of another commit
(``PYTHONPATH=<checkout>``): a PR that must leave a call as it was
compares that call's hash at its parent and at itself.
"""
import hashlib
import json
import sys

SPARSE = "paddle_tpu.ops.pallas.sparse_latent_attention"


def main():
    import importlib

    if "--with-sparse" in sys.argv[1:]:
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
                      sds((nb, bs, 256), f32)), stream

    def kv(cache_shape, **more):
        def call(q, k, v, kc, vc, *rest):
            return ragged_paged_attention(q, k, v, kc, vc, *rest,
                                          impl="interpret", **more)
        return call, (sds((t, 4, 128), f32), sds((t, 2, 128), f32),
                      sds((t, 2, 128), f32), sds(cache_shape, f32),
                      sds(cache_shape, f32)), stream

    def selected():
        # the `dots3-note-prev-d5.longdoc-c16` step's own shape: nothing
        # runs, a lowering costs what its trace costs
        t, s, mb, nb, bs = 512, 16, 2048, 32768, 16
        attend = importlib.import_module(SPARSE).sparse_latent_attention

        def call(q, new, cache, sel, *rest):
            return attend(q, new, cache, *rest, sel, impl="interpret",
                          v_lanes=512, scale=0.07)
        bf16 = jnp.bfloat16
        return call, (sds((t, 128, 640), bf16), sds((t, 640), bf16),
                      sds((nb, bs, 640), bf16), sds((t, mb * bs), jnp.int8)
                      ), (sds((s, mb), i32), sds((s + 1,), i32),
                          sds((s,), i32), sds((), i32))

    def lowered(name, call, shapes, stream):
        text = jax.jit(call).lower(*shapes, *stream).as_text()
        assert "ragged" in text or "_attend_pallas" in text, name
        return hashlib.sha256(text.encode()).hexdigest()

    out = {"kv": lowered("kv", *kv((nb, bs, 2, 128))),
           "kv_window_folded": lowered("kv_window_folded",
                                       *kv((nb, bs, 256), window=6))}
    if "--with-sparse" not in sys.argv[1:]:
        assert SPARSE not in sys.modules, (
            "a K/V call pulled the latent kernel's module in")
    out["latent"] = lowered("latent", *latent())
    out["latent_window"] = lowered("latent_window", *latent(window=6))
    out["selected"] = lowered("selected", *selected())
    print(json.dumps(out))


if __name__ == "__main__":
    main()
