"""A hyper-connection sublayer's passes over the residual streams as Pallas TPU
kernels: one pass over the streams a kernel, tiled over tokens.

A tile holds all n streams' whole d for ``TILE_TOKENS`` tokens, so everything
that needs a reduction over ``vec(X)`` is done where the data already is.

* ``maps_and_mix_in`` (pass A): the sum of squares, the phi product (the
  operands as they are held, float32 accumulation), ``H_pre = sigmoid`` and
  ``H_pre X`` rounded once.  Its backward is one pass too: ``d_H_pre``, the
  normed projection's term, ``H_pre^T d_mixed`` and phi's gradient (summed
  over the token grid), all added into the cotangent that pass B's backward
  wrote (the buffer is aliased: the streams' cotangent is written once a pass).
* ``mix_out`` (pass B): ``H_res X + H_post^T F``, each stream rounded as it is
  written; backward the streams' and the sublayer output's cotangents and the
  n n + n sums over d that are the maps' cotangents, tile-local.
* ``sinkhorn`` (the rounds in one kernel of their own, token-minor as the maps
  are held, the backward recomputing the rounds in VMEM).

What each keeps for the way back is what the plain path's ``jax.checkpoint``s
keep (models/lm/model.py): the streams as held, the sublayer's output, the
maps (and the raw maps with the inverse root mean square: 25 floats a token).
The arithmetic is the plain functions': float32 maps and mixes, one rounding
where a stream is written.  Which path runs is ``fits``'s answer: these
kernels on a TPU where the shape tiles, the plain functions everywhere else.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rt1_tpu.obs.trace import span

_LOG = logging.getLogger(__name__)

TILE_TOKENS = 128       # tokens of a tile: n x 128 x d of bfloat16 is 3.7 MB at n 4, d 3,584
ROWS = 16               # rows of a tile worked on at once (a bfloat16 vreg packs 16)
LANE_CHUNKS = (512, 256, 128)
VMEM_LIMIT = 100 << 20  # of the chip's 128 MiB

# Tests run the kernels on the CPU in Pallas' interpret mode by setting this
# (monkeypatch); nothing else does, and no configuration reaches it.
INTERPRET = False

_F32 = jnp.float32


def fits(tokens: int, hidden: int) -> bool:
    """Whether a sublayer over ``tokens`` positions of ``hidden``-wide streams
    takes the kernels: a TPU backend, the tokens a multiple of the tile, d a
    multiple of the lane width."""
    return ((INTERPRET or jax.default_backend() == "tpu")
            and tokens % TILE_TOKENS == 0 and hidden % 128 == 0)


@functools.lru_cache(maxsize=None)
def _announce(tile_tokens: int, streams: int, hidden: int, held_in: str,
              sinkhorn_in_kernel: bool) -> None:
    """Once a shape: which kernels a run timed, as a span and a log line."""
    chosen = dict(tile_tokens=tile_tokens, streams=streams, hidden=hidden, held_in=held_in,
                  sinkhorn_in_kernel=sinkhorn_in_kernel)
    _LOG.info("streams kernel: %s", chosen)
    with span("lm/streams_kernel", **chosen):
        pass


def _lanes(d: int) -> int:
    return next(c for c in LANE_CHUNKS if d % c == 0)


def _fold(x):
    """(rows, lanes) summed over its 128-wide lane tiles: (rows, 128)."""
    out = x[:, :128]
    for c in range(128, x.shape[1], 128):
        out = out + x[:, c:c + 128]
    return out


def _col(m, k: int):
    """Column k of a small (rows, K) array as (rows, 1)."""
    lane = lax.broadcasted_iota(jnp.int32, m.shape, 1)
    return jnp.sum(jnp.where(lane == k, m, 0.0), axis=-1, keepdims=True)


def _place(cols, width: int):
    """(rows, 1) columns side by side as (rows, width), zeros past them."""
    rows = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    out = jnp.zeros((rows, width), _F32)
    for k, c in enumerate(cols):
        out = jnp.where(lane == k, c, out)
    return out


def _row_chunks(tile: int, body) -> None:
    """``body(rows)`` for every ``ROWS`` rows of the tile, as a loop: what a
    chunk works on stays near the registers."""
    def step(r, carry):
        body(pl.ds(pl.multiple_of(r * ROWS, ROWS), ROWS))
        return carry
    lax.fori_loop(0, tile // ROWS, step, 0)


def _lane_chunks(d: int, body, carry=()):
    """``carry = body(lanes, carry)`` for every chunk of d's lanes: a loop, so
    that a chunk's operations are traced once and not once a chunk, unrolled
    where it is lowered (rolled, a trip is too short to hide its loads: pass
    A's backward 1.86 ms for 1.14)."""
    lanes = _lanes(d)

    def step(c, carry):
        return body(pl.ds(pl.multiple_of(c * lanes, lanes), lanes), carry)
    return lax.fori_loop(0, d // lanes, step, carry, unroll=True)


def _how():
    """The static arguments of a pass, read where it is called."""
    return dict(tile=TILE_TOKENS, interpret=INTERPRET)


# Every pass is a jitted function of its own: its kernel's body (a few thousand
# operations, unrolled) is traced and lowered once a shape and not once a call
# (xing's step calls each 12 to 24 times; traced each time, the step's first
# call took 170 s more).  XLA inlines the calls and puts the caller's scope
# before the kernel's own, so a profile still names every call's layer.
_pass = functools.partial(jax.jit, static_argnames=("tile", "interpret"))


def _call(kernel, name: str, interpret: bool, *, grid, in_specs, out_specs, out_shape,
          scratch_shapes=(), aliases=None, accumulates=False):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=list(scratch_shapes), input_output_aliases=aliases or {},
        interpret=interpret, name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if accumulates else "parallel",),
            vmem_limit_bytes=VMEM_LIMIT))


def _tokens(shape, tile):
    """Block of a token-major array (T, ...): ``tile`` tokens, the rest whole."""
    rest = tuple(shape[1:])
    return pl.BlockSpec((tile,) + rest, lambda t: (t,) + (0,) * len(rest))


def _streams(shape, tile):
    """Block of the streams (n, T, d): every stream's ``tile`` tokens."""
    return pl.BlockSpec((shape[0], tile, shape[2]), lambda t: (0, t, 0))


def _whole(shape):
    return pl.BlockSpec(tuple(shape), lambda t: (0,) * len(shape))


# -- pass B: H_res X + H_post^T F

def _mix_out_kernel(x_ref, f_ref, m_ref, o_ref):
    n, tile, d = x_ref.shape

    def body(rows):
        m = m_ref[rows, :]
        res = [[_col(m, i * n + j) for j in range(n)] for i in range(n)]
        post = [_col(m, n * n + i) for i in range(n)]

        def chunk(lanes, carry):
            xs = [x_ref[j, rows, lanes].astype(_F32) for j in range(n)]
            f = f_ref[rows, lanes].astype(_F32)
            for i in range(n):
                acc = res[i][0] * xs[0]
                for j in range(1, n):
                    acc = acc + res[i][j] * xs[j]
                o_ref[i, rows, lanes] = (acc + post[i] * f).astype(o_ref.dtype)
            return carry

        _lane_chunks(d, chunk)

    _row_chunks(tile, body)


def _mix_out_back_kernel(g_ref, x_ref, f_ref, m_ref, dx_ref, df_ref, dm_ref):
    n, tile, d = x_ref.shape

    def body(rows):
        m = m_ref[rows, :]
        res = [[_col(m, i * n + j) for j in range(n)] for i in range(n)]
        post = [_col(m, n * n + i) for i in range(n)]

        def chunk(lanes, sums):
            gs = [g_ref[i, rows, lanes].astype(_F32) for i in range(n)]
            xs = [x_ref[j, rows, lanes].astype(_F32) for j in range(n)]
            f = f_ref[rows, lanes].astype(_F32)
            for j in range(n):
                acc = res[0][j] * gs[0]
                for i in range(1, n):
                    acc = acc + res[i][j] * gs[i]
                dx_ref[j, rows, lanes] = acc.astype(dx_ref.dtype)
            acc = post[0] * gs[0]
            for i in range(1, n):
                acc = acc + post[i] * gs[i]
            df_ref[rows, lanes] = acc.astype(df_ref.dtype)
            return tuple(
                [sums[i * n + j] + _fold(gs[i] * xs[j]) for i in range(n) for j in range(n)]
                + [sums[n * n + i] + _fold(gs[i] * f) for i in range(n)])

        sums = _lane_chunks(d, chunk, (jnp.zeros((ROWS, 128), _F32),) * (n * n + n))
        dm_ref[rows, :] = _place(
            [jnp.sum(s, axis=-1, keepdims=True) for s in sums], dm_ref.shape[1])

    _row_chunks(tile, body)


@_pass
def _mix_out_pass(x, f, m, *, tile, interpret):
    with jax.named_scope("hyper_connection/mix"):
        return _call(
            _mix_out_kernel, "streams_mix_out", interpret, grid=(x.shape[1] // tile,),
            in_specs=[_streams(x.shape, tile), _tokens(f.shape, tile), _tokens(m.shape, tile)],
            out_specs=_streams(x.shape, tile),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, f, m)


@_pass
def _mix_out_back_pass(g, x, f, m, *, tile, interpret):
    with jax.named_scope("hyper_connection/mix"):
        return tuple(_call(
            _mix_out_back_kernel, "streams_mix_out_back", interpret, grid=(x.shape[1] // tile,),
            in_specs=[_streams(x.shape, tile), _streams(x.shape, tile),
                      _tokens(f.shape, tile), _tokens(m.shape, tile)],
            out_specs=[_streams(x.shape, tile), _tokens(f.shape, tile), _tokens(m.shape, tile)],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(f.shape, f.dtype),
                       jax.ShapeDtypeStruct(m.shape, _F32)])(g, x, f, m))


@jax.custom_vjp
def _mix_out(x, f, m):
    """x (n, T, d), f (T, d), m (T, n n + n) float32 [H_res row-major | H_post]."""
    return _mix_out_pass(x, f, m, **_how())


def _mix_out_fwd(x, f, m):
    return _mix_out_pass(x, f, m, **_how()), (x, f, m)


def _mix_out_bwd(kept, g):
    return _mix_out_back_pass(g, *kept, **_how())


_mix_out.defvjp(_mix_out_fwd, _mix_out_bwd)


def mix_out(streams, h_res, h_post, out):
    """``models.lm.model.mix_out`` by the kernels: streams (n, b, s, d), the
    maps token-minor as ``HyperConnection`` gives them, out (b, s, d)."""
    n, b, s, d = streams.shape
    with jax.named_scope("hyper_connection/mix"):
        maps = jnp.concatenate(
            [h_res.reshape(n * n, b * s), h_post.reshape(n, b * s)]).astype(_F32).T
    mixed = _mix_out(streams.reshape(n, b * s, d), out.reshape(b * s, d), maps)
    return mixed.reshape(streams.shape)


# -- pass A: the raw maps and H_pre X

def _maps_kernel(x_ref, w_ref, gb_ref, mixed_ref, raw_ref, inv_ref, ss_ref, pre_ref,
                 *, eps: float):
    n, tile, d = x_ref.shape
    product = None
    for j in range(n):      # (tile, d) x (K, d) -> (tile, K) on the MXU, float32 sums
        p = lax.dot_general(x_ref[j], w_ref[j], (((1,), (1,)), ((), ())),
                            preferred_element_type=_F32)
        product = p if product is None else product + p

    def squares(rows):
        def chunk(lanes, acc):
            for j in range(n):
                x = x_ref[j, rows, lanes].astype(_F32)
                acc = acc + _fold(x * x)
            return acc

        acc = _lane_chunks(d, chunk, jnp.zeros((ROWS, 128), _F32))
        ss_ref[rows, :] = jnp.sum(acc, axis=-1, keepdims=True)

    _row_chunks(tile, squares)
    inverse_rms = lax.rsqrt(ss_ref[...] / (n * d) + eps)
    raw = product * inverse_rms
    raw_ref[...] = raw
    inv_ref[...] = inverse_rms
    # gate and bias are zero past H_pre's n columns: only those are read below
    pre_ref[...] = jax.nn.sigmoid(raw * gb_ref[0:1, :] + gb_ref[1:2, :])

    def mix(rows):
        h = pre_ref[rows, :]
        pre = [_col(h, j) for j in range(n)]

        def chunk(lanes, carry):
            acc = pre[0] * x_ref[0, rows, lanes].astype(_F32)
            for j in range(1, n):
                acc = acc + pre[j] * x_ref[j, rows, lanes].astype(_F32)
            mixed_ref[rows, lanes] = acc.astype(mixed_ref.dtype)
            return carry

        _lane_chunks(d, chunk)

    _row_chunks(tile, mix)


def _maps_back_kernel(x_ref, dxb_ref, dmixed_ref, w_ref, gb_ref, raw_ref, inv_ref, draw_ref,
                      dx_ref, dw_ref, dz_ref, pre_ref, coef_ref, dpre_ref, term_ref):
    n, tile, d = x_ref.shape
    width = raw_ref.shape[1]
    gate = gb_ref[0:1, :]
    raw, inverse_rms = raw_ref[...], inv_ref[...]
    pre = jax.nn.sigmoid(raw * gate + gb_ref[1:2, :])
    pre_ref[...] = pre

    def pre_sums(rows):        # d_H_pre[j] = sum over d of d_mixed X_j
        def chunk(lanes, sums):
            dm = dmixed_ref[rows, lanes].astype(_F32)
            return tuple(sums[j] + _fold(dm * x_ref[j, rows, lanes].astype(_F32))
                         for j in range(n))

        sums = _lane_chunks(d, chunk, (jnp.zeros((ROWS, 128), _F32),) * n)
        dpre_ref[rows, :] = _place([jnp.sum(s, axis=-1, keepdims=True) for s in sums], width)

    _row_chunks(tile, pre_sums)
    dz = dpre_ref[...] * pre * (1.0 - pre)      # zero past the n columns, as d_H_pre is
    dz_ref[...] = dz
    draw = draw_ref[...] + dz * gate
    # raw = r (X W): d_(X W) = r d_raw, and through r, d_X = -r^3 X / (n d) sum_k d_raw (X W)
    coef_ref[...] = (-jnp.sum(draw * raw, axis=-1, keepdims=True)
                     * inverse_rms * inverse_rms / (n * d))
    dproduct = (draw * inverse_rms).astype(w_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    for j in range(n):
        dw_ref[j] += lax.dot_general(dproduct, x_ref[j], (((0,), (0,)), ((), ())),
                                     preferred_element_type=_F32)
        term_ref[...] = lax.dot_general(dproduct, w_ref[j], (((1,), (0,)), ((), ())),
                                        preferred_element_type=_F32)

        def add(rows, j=j):
            h = _col(pre_ref[rows, :], j)
            coef = coef_ref[rows, :]

            def chunk(lanes, carry):
                dx = (dxb_ref[j, rows, lanes].astype(_F32)
                      + h * dmixed_ref[rows, lanes].astype(_F32)
                      + coef * x_ref[j, rows, lanes].astype(_F32)
                      + term_ref[rows, lanes])
                dx_ref[j, rows, lanes] = dx.astype(dx_ref.dtype)
                return carry

            _lane_chunks(d, chunk)

        _row_chunks(tile, add)


@functools.partial(jax.jit, static_argnames=("eps", "tile", "interpret"))
def _maps_pass(x, w, gb, *, eps, tile, interpret):
    tokens, d, width = x.shape[1], x.shape[2], w.shape[1]
    with jax.named_scope("hyper_connection/maps"):
        return tuple(_call(
            functools.partial(_maps_kernel, eps=eps), "streams_maps", interpret,
            grid=(tokens // tile,),
            in_specs=[_streams(x.shape, tile), _whole(w.shape), _whole(gb.shape)],
            out_specs=[_tokens((tokens, d), tile), _tokens((tokens, width), tile),
                       _tokens((tokens, 1), tile)],
            out_shape=[jax.ShapeDtypeStruct((tokens, d), x.dtype),
                       jax.ShapeDtypeStruct((tokens, width), _F32),
                       jax.ShapeDtypeStruct((tokens, 1), _F32)],
            scratch_shapes=[pltpu.VMEM((tile, 1), _F32), pltpu.VMEM((tile, width), _F32)],
        )(x, w, gb))


@_pass
def _maps_back_pass(x, dxb, dmixed, w, gb, raw, inverse_rms, draw, *, tile, interpret):
    tokens, d, width = x.shape[1], x.shape[2], w.shape[1]
    with jax.named_scope("hyper_connection/maps"):
        dx, dw, dz = _call(
            _maps_back_kernel, "streams_maps_back", interpret, grid=(tokens // tile,),
            in_specs=[_streams(x.shape, tile), _streams(x.shape, tile),
                      _tokens((tokens, d), tile), _whole(w.shape), _whole(gb.shape),
                      _tokens(raw.shape, tile), _tokens(inverse_rms.shape, tile),
                      _tokens(raw.shape, tile)],
            out_specs=[_streams(x.shape, tile), _whole(w.shape), _tokens(raw.shape, tile)],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct(w.shape, _F32),
                       jax.ShapeDtypeStruct(raw.shape, _F32)],
            scratch_shapes=[pltpu.VMEM((tile, width), _F32), pltpu.VMEM((tile, 1), _F32),
                            pltpu.VMEM((tile, width), _F32), pltpu.VMEM((tile, d), _F32)],
            aliases={1: 0},     # the cotangent pass B's backward wrote is added to in place
            accumulates=True,   # phi's gradient is summed over the token grid
        )(x, dxb, dmixed, w, gb, raw, inverse_rms, draw)
        dgb = jnp.stack([jnp.sum(dz * raw, axis=0), jnp.sum(dz, axis=0)])
    return dx, dw.astype(w.dtype), dgb


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _maps(x, w, gb, eps):
    """x (n, T, d); w (n, K, d) as x is held; gb (2, K) float32, H_pre's gates
    and biases in its first n columns and zeros after.  Returns H_pre X (T, d),
    the raw maps r (X W) (T, K) float32, and x itself: the sublayer's other
    reader of the streams (pass B) takes it from here, so that its cotangent
    comes back through this function's backward and is added to in place."""
    return _maps_fwd(x, w, gb, eps)[0]


def _maps_fwd(x, w, gb, eps):
    mixed, raw, inverse_rms = _maps_pass(x, w, gb, eps=eps, **_how())
    return (mixed, raw, x), (x, w, gb, raw, inverse_rms)


def _maps_bwd(eps, kept, cotangents):
    del eps
    x, w, gb, raw, inverse_rms = kept
    dmixed, draw, dxb = cotangents
    return _maps_back_pass(x, dxb, dmixed, w, gb, raw, inverse_rms, draw, **_how())


_maps.defvjp(_maps_fwd, _maps_bwd)


def maps_and_mix_in(streams, weights, gate, bias, eps: float):
    """Pass A.  streams (n, b, s, d); weights (n, d, K) as ``HyperConnection``
    makes them (the norm's scale folded into phi, cast as the streams are
    held); gate, bias: H_pre's n gates and biases.  Returns ``H_pre X`` (b, s,
    d), the raw maps (K, b, s) float32 as ``_normed_projection`` gives them,
    and the streams for pass B."""
    n, b, s, d = streams.shape
    width = weights.shape[-1]
    _announce(TILE_TOKENS, n, d, jnp.dtype(streams.dtype).name, SINKHORN_IN_KERNEL)
    with jax.named_scope("hyper_connection/maps"):
        gb = jnp.zeros((2, width), _F32).at[:, :n].set(jnp.stack([gate, bias]).astype(_F32))
        w = weights.astype(streams.dtype).transpose(0, 2, 1)
    mixed, raw, held = _maps(streams.reshape(n, b * s, d), w, gb, eps)
    with jax.named_scope("hyper_connection/maps"):
        raw = raw.T.reshape(width, b, s)
    return mixed.reshape(b, s, d), raw, held.reshape(streams.shape)


# -- the Sinkhorn rounds

# The rounds in a kernel of their own (False: ``models.lm.model.sinkhorn``
# between the two passes, XLA's ~120 small ops a sublayer a way).  The probe
# flips it to time both (scripts/lm_kernel_probe.py --only streams).
SINKHORN_IN_KERNEL = True
SINKHORN_SUBLANES = 8       # x 128 tokens a grid step


def rounds():
    """What ``HyperConnection`` runs the rounds with on this path (None: the
    plain function)."""
    return sinkhorn if SINKHORN_IN_KERNEL else None


def _groups(n: int):
    """The index sets a round divides by the sum of: every row, then every
    column, of an (n, n) map held row-major."""
    return ([[i * n + j for j in range(n)] for i in range(n)],
            [[i * n + j for i in range(n)] for j in range(n)])


def _sinkhorn_kernel(z_ref, o_ref, *, n, iters, eps, clamp):
    def one(_, m):
        m = list(m)
        for groups in _groups(n):
            for group in groups:
                total = m[group[0]]
                for k in group[1:]:
                    total = total + m[k]
                for k in group:
                    m[k] = m[k] / (total + eps)
        return tuple(m)

    m = lax.fori_loop(0, iters, one,
                      tuple(jnp.exp(jnp.clip(z_ref[k], *clamp)) for k in range(n * n)))
    for k in range(n * n):
        o_ref[k] = m[k]


def _sinkhorn_back_kernel(z_ref, dy_ref, dz_ref, y_ref, s_ref, *, n, iters, eps, clamp):
    """The rounds again, every half-round's output and sums kept in VMEM, then
    their transposes last to first: y = m / s with s = sum(m) + eps gives
    dm = (dy - sum(dy y)) / s."""
    z = [z_ref[k] for k in range(n * n)]
    start = tuple(jnp.exp(jnp.clip(zk, *clamp)) for zk in z)

    def forth(r, m):
        m = list(m)
        for half, groups in enumerate(_groups(n)):
            for g, group in enumerate(groups):
                total = m[group[0]]
                for k in group[1:]:
                    total = total + m[k]
                total = total + eps
                s_ref[2 * r + half, g] = total
                for k in group:
                    m[k] = m[k] / total
                    y_ref[2 * r + half, k] = m[k]
        return tuple(m)

    def back(i, dm):
        dm, r = list(dm), iters - 1 - i
        for half, groups in reversed(list(enumerate(_groups(n)))):
            for g, group in enumerate(groups):
                inner = dm[group[0]] * y_ref[2 * r + half, group[0]]
                for k in group[1:]:
                    inner = inner + dm[k] * y_ref[2 * r + half, k]
                for k in group:
                    dm[k] = (dm[k] - inner) / s_ref[2 * r + half, g]
        return tuple(dm)

    lax.fori_loop(0, iters, forth, start)
    dm = lax.fori_loop(0, iters, back, tuple(dy_ref[k] for k in range(n * n)))
    for k in range(n * n):
        inside = (z[k] >= clamp[0]) & (z[k] <= clamp[1])
        dz_ref[k] = jnp.where(inside, dm[k] * start[k], 0.0)


@functools.partial(jax.jit, static_argnames=("back", "iters", "eps", "clamp", "interpret"))
def _sinkhorn_pass(*operands, back: bool, iters, eps, clamp, interpret):
    """The rounds (or, ``back``, their transposes) over (n, n, b, s) operands."""
    n = operands[0].shape[0]
    flat = [a.astype(_F32).reshape(n * n, -1, 128) for a in operands]
    shape = flat[0].shape       # (n n, T / 128, 128)
    sub = SINKHORN_SUBLANES if shape[1] % SINKHORN_SUBLANES == 0 else shape[1]
    block = pl.BlockSpec((shape[0], sub, 128), lambda t: (0, t, 0))
    kernel = _sinkhorn_back_kernel if back else _sinkhorn_kernel
    with jax.named_scope("hyper_connection/maps"):
        out = _call(
            functools.partial(kernel, n=n, iters=iters, eps=eps, clamp=clamp),
            "streams_sinkhorn_back" if back else "streams_sinkhorn", interpret,
            grid=(shape[1] // sub,), in_specs=[block] * len(flat), out_specs=block,
            out_shape=jax.ShapeDtypeStruct(shape, _F32),
            scratch_shapes=[pltpu.VMEM((2 * iters, width, sub, 128), _F32)
                            for width in ((n * n, n) if back else ())],
        )(*flat)
    return out.reshape(operands[0].shape).astype(operands[0].dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def sinkhorn(logits, iters: int, eps: float, clamp):
    """``models.lm.model.sinkhorn`` over (n, n, b, s) logits, b s a multiple of
    128: the rounds of 1,024 tokens a grid step on whole vregs."""
    return _sinkhorn_pass(logits, back=False, iters=iters, eps=eps, clamp=tuple(clamp),
                          interpret=INTERPRET)


def _sinkhorn_fwd(logits, iters, eps, clamp):
    return sinkhorn(logits, iters, eps, clamp), logits


def _sinkhorn_bwd(iters, eps, clamp, logits, dy):
    return (_sinkhorn_pass(logits, dy, back=True, iters=iters, eps=eps, clamp=tuple(clamp),
                           interpret=INTERPRET),)


sinkhorn.defvjp(_sinkhorn_fwd, _sinkhorn_bwd)
