"""Reference for the prefix trie's commit (tests only).

:func:`commit_prefix_from_zero` is ``BlockManager.commit_prefix`` as it
stood before the manager kept a cursor per request: every call starts at
block 0 and slices, keys and hashes every full block under ``covered``
again. Moved here unchanged (``self`` became ``bm``); the registration rule
it states is the specification. :class:`OracleBlockManager` is a
``BlockManager`` whose commit is that walk: driven through the same calls
as the program's, its five trie maps say what the program's must hold.
"""
from typing import Optional, Sequence

from paddle_tpu.serving.block_manager import BlockManager, _fold_hash

TRIE_MAPS = ("_prefix_index", "_block_key", "_key_hash", "_hash_key",
             "_hash_tokens")


def commit_prefix_from_zero(bm: BlockManager, request_id: str,
                            tokens: Sequence[int], covered: int):
    if not bm.enable_prefix_cache:
        return
    table = bm._tables.get(request_id)
    if table is None:
        return
    bs = bm.block_size
    limit = min(covered, len(tokens))
    key: Optional[tuple] = None
    chash: Optional[str] = None
    idx = 0
    while (idx + 1) * bs <= limit:
        part = tuple(tokens[idx * bs:(idx + 1) * bs])
        key = (key, part)
        chash = _fold_hash(chash, part)
        b = table[idx]
        if key in bm._prefix_index:
            # someone committed this prefix first; keep their block
            idx += 1
            continue
        if b not in bm._block_key:
            bm._prefix_index[key] = b
            bm._block_key[b] = key
            bm._key_hash[key] = chash
            bm._hash_key[chash] = key
            bm._hash_tokens[chash] = (idx + 1) * bs
            bm._trie_rev += 1
        idx += 1


class OracleBlockManager(BlockManager):
    def commit_prefix(self, request_id, tokens, covered):
        commit_prefix_from_zero(self, request_id, tokens, covered)


def trie_maps(bm: BlockManager) -> dict:
    return {name: dict(getattr(bm, name)) for name in TRIE_MAPS}
