"""Shared incremental-decode machinery for KV-cache decoder cells
(transformer_nmt.TransformerDecodeCell and gpt.GPTDecodeCell).

One decode step at position ``pos`` needs three masks derived from the
static cache length ``tmax``: a one-hot cache-write selector, its
complement, and the <=pos additive visibility mask. Keeping them (and
the head-split attention) here means a fix to the cache-write or
masking logic lands in every decoder at once.
"""
import collections

import numpy as np

from paddle_tpu.fluid import layers

__all__ = ["attend", "attend_cached", "split_heads", "step_masks",
           "update_cache", "StateEntry", "DecodeModel", "MediaEncoder",
           "require_rows_only"]


class StateEntry(collections.namedtuple(
        "StateEntry", ["name", "shape", "dtype", "kind"])):
    """One piece of the state a served model carries per sequence: a
    ``name``, its per-slot ``shape``, its ``dtype`` and its ``kind``:

    - ``"rows"``: one row per position (K or V of an attention layer);
      the leading axis is the cache length, a prefix of the sequence is
      the first rows, and cutting at a prefix is zeroing the rest;
    - ``"fixed"``: a whole value per sequence (a convolution window, a
      state-space state); it has no rows to cut or to share;
    - ``"ring"``: the last ``shape[0]`` positions' rows of a window
      attention layer, position p at row ``p mod shape[0]``: it does not
      grow with the sequence, and what it held of an earlier position is
      written over, so it has no prefix to cut or to share either.
    """

    __slots__ = ()

    @property
    def nbytes(self):
        return int(np.prod(self.shape)) * np.dtype(self.dtype).itemsize


class MediaEncoder:
    """The encoder of a model whose prompts may hold MEDIA rows: positions
    that take, in place of their token's embedding, a row an encoder made
    from an image the request carries.

    ``build(cfg, patches)`` builds the encoder's program over ONE image
    padded to ``patches`` rows of ``patch_width`` uint8 values (one program a
    bucket of ``buckets``, ascending; the largest is the most patches an
    image may hold): it feeds the patches ``(1, patches, patch_width)`` and
    the image's grid ``(1, 2)`` int64 ``[h, w]`` (``feed_names``) and fetches
    the image's rows ``(patches / (merge[0] merge[1]), width)``, the real ones
    first: ``merge`` is the block of patches one row is made of, and a grid's
    sides are whole numbers of it. ``max_grid`` is the most patches a side
    may hold (the sides of the encoder's learned position table: its program
    resizes the table DOWN to a grid and has no row or column past them).
    :meth:`check_grid` is the one place that says which grids the programs
    take. ``media_id`` is the token id that marks a position taking a media
    row; a prompt holds as many of them as its images give rows, and takes
    the rows in order. ``patchify(pixels)`` (host code) turns one image's
    uint8 pixels ``(patch h, patch w, 3)`` into the ``(h w, patch_width)``
    uint8 rows the program is fed, ``patch`` the pixels a patch's side holds.
    The model's fill programs (prefill and chunk) then feed, beside ids and
    lengths, the request's rows ``(buffer_rows, width)`` (every image's one
    after the other, then padding) and per position the row it takes or -1
    (``media_feed_names``: the buffer, the index)."""

    def __init__(self, build, buckets, media_id, width, merge, max_grid,
                 buffer_rows, max_images, patch_width, patch, patchify):
        self.build, self.patchify, self.patch = build, patchify, int(patch)
        self.buckets = tuple(sorted(int(b) for b in buckets))
        self.media_id, self.width = int(media_id), int(width)
        self.merge = (int(merge[0]), int(merge[1]))
        self.max_grid = (int(max_grid[0]), int(max_grid[1]))
        self.buffer_rows, self.max_images = int(buffer_rows), int(max_images)
        self.patch_width = int(patch_width)

    def rows_of(self, patches):
        """Media rows an image of ``patches`` patches gives."""
        return int(patches) // (self.merge[0] * self.merge[1])

    def bucket_for(self, patches):
        return next((b for b in self.buckets if b >= patches), None)

    def check_grid(self, h, w):
        """``ValueError`` in words unless the encoder's programs take an
        image of ``h x w`` patches."""
        (mh, mw), (top, wide) = self.merge, self.max_grid
        if h < mh or w < mw or h % mh or w % mw:
            raise ValueError(
                "a grid of %d x %d patches: a side is a whole number of the "
                "%d x %d patches a media row is made of" % (h, w, mh, mw))
        if h > top or w > wide:
            raise ValueError(
                "a grid of %d x %d patches: a side holds at most %d x %d "
                "(the sides of the encoder's position table)"
                % (h, w, top, wide))
        if self.bucket_for(h * w) is None:
            raise ValueError(
                "a grid of %d x %d holds %d patches; at most %d"
                % (h, w, h * w, self.buckets[-1]))


class DecodeModel:
    """What a served model hands ``serving.DecodeEngine``: its program
    builders and the declaration of the state they carry.

    ``state`` lists the :class:`StateEntry` of every buffer, in the order
    of the step program's ``cache_feed_names`` (and of its fetches after
    the first). ``build_prefill(cfg, bucket, cache_len)`` and
    ``build_step(cfg, cache_len)`` build the two programs into the
    current default program; ``build_delta`` / ``build_verify`` are the
    suffix-prefill and block-verify builders of a model that has them
    (rows-only models). A prefill fetches the first token and then the
    sequence's state; ``unpack(*fetched)`` turns those fetches into one
    ``(1,) + entry.shape`` array per entry and ``pack(rows)`` turns one
    slot's per-entry arrays into the stacked groups of the wire format
    (both are traced inside one jitted call; identity by default).
    ``build_chunk(cfg, rows, cache_len)``, with ``chunk_rows`` the rows it
    wants, is the builder of a model whose prefill can CONTINUE: a program
    over ``rows`` positions of a prompt that feeds the ids ``(1, rows)``,
    the real tokens of this chunk ``(1, 1)``, the row of its first position
    ``(1, 1)`` (``feed_names[:3]``) and the sequence's state so far, one
    ``(1,) + entry.shape`` array per entry in the declaration's order
    (``cache_feed_names``, donated; zeros before the first chunk), and
    fetches the greedy token after the chunk's last real position and the
    state carried on, in the same order: after the last chunk, what
    ``build_prefill`` fetches. The engine then fills a long prompt beside
    live streams a chunk a turn, with a decode step between two chunks
    (``DecodeEngine._loop``); ``chunk_rows`` is the run length the model's
    own programs already cut a prompt by, not a knob. A model without one
    (the default) is filled by its bucket programs alone.
    ``encoder`` (a :class:`MediaEncoder`) is declared by a model whose
    requests may carry images: the engine runs its program on the device, an
    image a turn, and the fill programs take the rows at the marked
    positions. Such a request is refused, in words, by everything that
    re-runs or ships a prompt as ids alone (the prefix pool, the session
    tier, a draft, the wire).
    ``step_counters(aux, live)`` maps the step program's trailing fetch
    (after the state) to lifetime counters of ``DecodeEngine.stats()``.
    ``rows_are_kv`` says that the ``rows`` entries are the K and the V
    of attention layers, all of one width (with their scales, if
    quantised): the geometry the prefix pool, the session tier, the wire
    format, the int8 step and the delta / verify builders know. The one
    model whose rows are of another make (a latent row and an indexer's
    row of unequal widths) sets it False.
    """

    def __init__(self, cfg, state, build_prefill, build_step,
                 build_delta=None, build_verify=None, unpack=None,
                 pack=None, step_counters=None, rows_are_kv=True,
                 build_chunk=None, chunk_rows=None, encoder=None):
        self.cfg = cfg
        self.encoder = encoder
        self.state = list(state)
        self.rows_are_kv = bool(rows_are_kv)
        self.build_prefill = build_prefill
        self.build_step = build_step
        self.build_delta = build_delta
        self.build_verify = build_verify
        if (build_chunk is None) != (chunk_rows is None):
            raise ValueError("build_chunk and chunk_rows come together")
        self.build_chunk = build_chunk
        self.chunk_rows = chunk_rows and int(chunk_rows)
        self.unpack = unpack or (lambda *vals: list(vals))
        self.pack = pack or (lambda rows: list(rows))
        self.step_counters = step_counters

    def slot_bytes(self, kind=None):
        """Device bytes one slot's state occupies (of one ``kind``)."""
        return sum(e.nbytes for e in self.state
                   if kind is None or e.kind == kind)


def require_rows_only(model, feature):
    """Features that cut, share, quantise or ship a sequence's state row
    by row cannot hold a ``fixed`` or a ``ring`` entry (the message says
    what each kind would need of them), and they know rows
    only as the K and the V of attention layers, all of one width
    (``DecodeModel.rows_are_kv``): refuse anything else, do not emulate."""
    other = [e for e in model.state if e.kind != "rows"]
    if other:
        names = [e.name for e in other]
        kinds = sorted({e.kind for e in other})
        what = {"fixed": "a fixed-size state per sequence",
                "ring": "a ring of a window layer's last positions"}
        need = {"fixed": "a recurrent state (a convolution window, a "
                         "state-space or a delta-rule state) can be cut or "
                         "shared only where a fill took a snapshot of it, "
                         "at a position chosen beforehand, and rolled back "
                         "only to a copy kept from before the steps to undo",
                "ring": "a ring has written over what it held of the "
                        "earlier positions"}
        raise ValueError(
            "%s needs state with one row per position; this model also "
            "carries %s (%s%s), which has no prefix to cut, share or roll "
            "back: %s; nothing here takes such a snapshot or keeps such a "
            "copy" % (feature, " and ".join(what.get(k, k) for k in kinds),
                      ", ".join(names[:3]),
                      ", ..." if len(names) > 3 else "",
                      "; ".join(need[k] for k in kinds if k in need)))
    if not model.rows_are_kv:
        shapes = sorted({(e.name.rsplit("_", 1)[0], e.shape[-1])
                         for e in model.state})
        raise ValueError(
            "%s needs rows that are the K and the V of attention layers, "
            "all of one width; this model's rows are not K and V of one "
            "width (%s): nothing here cuts, packs, quantises or ships them"
            % (feature, ", ".join("%s %d wide" % s for s in shapes)))


def split_heads(t, heads, dh):
    """(B, T, heads*dh) -> (B, heads, T, dh). Reshape + transpose on a
    contiguous input — XLA folds the permutation into the consuming
    dot_general (a mid-axis slice+squeeze formulation moves 27% more
    HLO copy traffic per BERT step)."""
    t = layers.reshape(t, [0, 0, heads, dh])
    return layers.transpose(t, [0, 2, 1, 3])


def attend(q, k, v, mask, heads, hidden):
    """q (B,Tq,H), k/v (B,Tk,H), additive mask broadcastable to
    (B,nh,Tq,Tk) -> context (B,Tq,H)."""
    dh = hidden // heads

    def split(t):
        return split_heads(t, heads, dh)

    scores = layers.matmul(split(q), split(k), transpose_y=True,
                           alpha=dh ** -0.5)
    if mask is not None:
        scores = layers.elementwise_add(scores, mask)
    ctx = layers.matmul(layers.softmax(scores), split(v))
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    return layers.reshape(ctx, [0, 0, hidden])


def attend_cached(q, k, v, mask, heads, hidden):
    """:func:`attend` for a few query rows over a long cache, reading the
    caches where they lie: q (S, K, H), k/v (S, T, H) slot caches,
    additive mask (S, 1, K, T) -> context (S, K, H).

    :func:`attend` splits k and v by head, which makes the head a batch
    dimension of both products; with one query row per head the TPU
    compiler then copies each whole cache into a transposed layout
    before it multiplies. Here the head stays inside the query: every
    query row is spread over ``heads`` rows that are zero outside their
    own head's columns, so the scores are ONE batched product
    ``(S, heads*K, H) x (S, T, H)^T`` contracting the caches' minor
    dimension, and the context ONE product ``(S, heads*K, T) x (S, T,
    H)`` of which each head keeps its own columns. The zeros add
    nothing, so every score and context element is the same sum of the
    same products as in :func:`attend`; the extra multiplications are
    ``heads`` times a product that was one row wide."""
    dh = hidden // heads
    kq = q.shape[1]
    head_cols = layers.assign(np.repeat(
        np.eye(heads, dtype="float32"), dh, axis=1))     # (heads, H)
    head_cols = layers.unsqueeze(head_cols, [0, 2])      # (1, nh, 1, H)
    q_heads = layers.elementwise_mul(
        layers.unsqueeze(q, [1]), head_cols)             # (S, nh, K, H)
    scores = layers.matmul(
        layers.reshape(q_heads, [0, heads * kq, hidden]), k,
        transpose_y=True, alpha=dh ** -0.5)              # (S, nh*K, T)
    scores = layers.reshape(scores, [0, heads, kq, -1])
    if mask is not None:
        scores = layers.elementwise_add(scores, mask)
    probs = layers.reshape(layers.softmax(scores), [0, heads * kq, -1])
    ctx = layers.reshape(layers.matmul(probs, v),
                         [0, heads, kq, hidden])         # (S, nh, K, H)
    return layers.reduce_sum(
        layers.elementwise_mul(ctx, head_cols), dim=1)   # (S, K, H)


def step_masks(pos, tmax):
    """For a (B, 1) int64 position: returns (write3, keep3, self_mask)
    — the (B, T, 1) one-hot cache-write selector, its complement, and
    the (B, 1, 1, T) additive mask hiding positions > pos."""
    steps = layers.unsqueeze(
        layers.range(0, tmax, 1, "int64"), [0])          # (1, T)
    write = layers.cast(layers.equal(steps, pos), "float32")
    write3 = layers.unsqueeze(write, [2])                # (B, T, 1)
    keep3 = layers.scale(write3, scale=-1.0, bias=1.0)
    seen = layers.cast(
        layers.less_equal(steps, pos), "float32")        # (B, T)
    self_mask = layers.scale(seen, scale=1e9, bias=-1e9)
    self_mask = layers.unsqueeze(self_mask, [1, 2])      # (B, 1, 1, T)
    return write3, keep3, self_mask


def update_cache(cache, new_t, write3=None, keep3=None, pos=None,
                 per_row=False):
    """Write the (B, 1, H) step value into the (B, T, H) cache.

    With ``pos`` (the (B, 1) decode position) this is an O(B·H)
    dynamic-update-slice write: uniform across the batch by default
    (every row advances one token per scan step, as in the full-batch
    decoders here), or an independent position per row with
    ``per_row=True`` (slotted continuous-batching decode, where a
    freshly prefilled slot sits at its prompt length while neighbours
    are deep into generation). Without ``pos``, the one-hot masked
    rewrite (``write3``/``keep3`` from :func:`step_masks`) re-reads and
    re-writes the whole cache — kept for callers with neither."""
    if pos is not None:
        from paddle_tpu.fluid.layer_helper import LayerHelper

        helper = LayerHelper("decode_cache_write")
        out = helper.create_variable_for_type_inference(dtype=cache.dtype)
        out.shape = cache.shape
        helper.append_op(
            type="decode_cache_write",
            inputs={"Cache": [cache], "Value": [new_t], "Pos": [pos]},
            outputs={"Out": [out]},
            attrs={"per_row": bool(per_row)},
        )
        return out
    if write3 is None or keep3 is None:
        raise ValueError(
            "update_cache needs either pos (uniform-position fast "
            "path) or the write3/keep3 masks from step_masks")
    return layers.elementwise_add(
        layers.elementwise_mul(cache, keep3),
        layers.elementwise_mul(new_t, write3))
