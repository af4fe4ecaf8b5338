"""The gated short convolution (the LFM2 family's ``Lfm2ShortConv``) as
functions of a window of positions, the tail handed in and handed back,
or of one decode step of a layer's entries in their order.

The layer's two gates are its projections' (ops/transformer_ops.py
``_conv_mixer``: ``[B | C | z] = u W_in``, ``g = B * z``, out ``= (C * c)
W_out``); what lies between them is this module's, for an input ``g_t``
[C] (C channels, k taps a channel)::

    c_t = sum_{j<k} w[j] * g_{t-(k-1)+j}                     (zeros before 0)

a causal depthwise convolution with NO bias and NO activation (ops/ssm.py's
``conv_window`` has both). What a sequence carries from one call to the
next is the TAIL alone, its last ``k - 1`` inputs ``g`` [k - 1, C]: the
mixer has NO recurrent state, so its cache of the ``state`` kind is ONE
pool, ``[layers, entries, (k - 1) * C]``, an entry flat at whole lane
tiles as ops/ssm.py ``conv_step_flat`` takes it (PERF.md section 6, PR
49). As there, the tail is protected by no length mask: the caller starts
a new sequence from zeros and never from what an entry held.

Parameters ``p`` of one layer, by slot: ``ConvW`` [k, C], tap ``j`` on the
input ``k - 1 - j`` positions back (a Conv1d weight's own order).
"""
import jax
import jax.numpy as jnp

__all__ = ["window", "step", "step_in_kernel", "taps_window", "taps_step"]

_F32 = jnp.float32


def _weighted(parts, w):
    """sum_j w[j] * parts[j] in float32: parts k arrays [..., C], w [k, C]."""
    wf = w.astype(_F32)
    acc = parts[0].astype(_F32) * wf[0]
    for j in range(1, len(parts)):
        acc = acc + parts[j].astype(_F32) * wf[j]
    return acc


def taps_window(g, tail0, w, lens):
    """The taps over a window. g [B, T, C]; tail0 [B, k - 1, C], the inputs
    before the window's first (zeros at a sequence's start); w [k, C];
    lens [B], the rows' real positions. Returns (c [B, T, C] in g's type,
    the tail the window leaves: the last k - 1 REAL inputs)."""
    t, k = g.shape[1], w.shape[0]
    full = jnp.concatenate([tail0.astype(g.dtype), g], axis=1)
    acc = _weighted([full[:, j:j + t] for j in range(k)], w)
    # input t lies at full[t + k - 1]: the last k - 1 real ones start at
    # full[lens]
    at = lens[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return acc.astype(g.dtype), tail


def taps_step(g, tail0, w):
    """``taps_window`` for one position an entry, the tail FLAT as its pool
    stores it: g [n, C], tail0 [n, (k - 1) * C], oldest first -> (c [n, C],
    tail [n, (k - 1) * C]). Every tap is a whole-tile slice of the entry
    where C is whole lane tiles."""
    k, width = w.shape
    taps = [tail0[:, j * width:(j + 1) * width] for j in range(k - 1)] + [g]
    return _weighted(taps, w).astype(g.dtype), \
        jnp.concatenate(taps[1:], axis=1).astype(tail0.dtype)


def window(p, z, state0, tail0, lens, eps):
    """A window of positions through the taps, in the form the state
    mixers share (ops/ssm.py ``window``): z [B, T, C] the gated input;
    ``state0`` None, the mixer keeps no state; tail0 [B, k - 1, C]; lens
    [B]. Returns (c [B, T, C] in z's type, None, tail [B, k - 1, C])."""
    with jax.named_scope("mixer/conv/taps"):
        c, tail = taps_window(z, tail0, p["ConvW"], lens)
    return c, None, tail


def step_in_kernel(pool_shape, pool_dtype):
    """Whether ``step`` runs a Pallas kernel over a pool of this shape and
    type: never, three multiply-adds an entry are jax.numpy's."""
    return False


def step(p, z, s_pool, layer, held, tail0, eps):
    """A decode step of one layer IN THE ENTRIES' ORDER, in the form the
    state mixers share: z [n, C], an entry's gated input; ``s_pool`` None;
    tail0 [n, (k - 1) * C], flat as the tail pool stores it -> (c [n, C],
    None, tail [n, (k - 1) * C]); which entries keep their tail is the
    caller's (``held``)."""
    with jax.named_scope("mixer/conv/taps"):
        c, tail = taps_step(z, tail0, p["ConvW"])
    return c, None, tail
