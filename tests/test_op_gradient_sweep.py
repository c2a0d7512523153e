"""Registry-wide numeric-gradient sweep (VERDICT r4 item 6).

Reference idiom: ``tests/python/unittest/test_operator.py`` gradient-checks
essentially every differentiable operator (file-level citation, SURVEY.md
caveat). Here one classified table covers the ENTIRE op registry:

  - ``GRAD_CASES``  — differentiable ops, checked against central finite
    differences via ``check_numeric_gradient`` on small shapes (inputs
    chosen away from kinks: offsets for relu/abs, SPD matrices for
    Cholesky, distinct values for max/sort, ...).
  - ``NONDIFF``     — ops whose outputs are integer/boolean/assignment
    results, value-independent, or zero-gradient by definition.
  - ``CUSTOM_GRAD`` — training heads whose forward is a pass-through and
    whose backward injects the loss gradient by design (numeric diff of
    the forward cannot match: SoftmaxOutput & friends).
  - ``SKIP``        — differentiable but excluded here with an explicit
    reason (stochastic samplers, decomposition gradients covered by
    dedicated tests, fused packed-parameter RNN).

``test_registry_fully_classified`` fails when a newly registered op is
not in exactly one bucket, so the sweep can never silently go stale.
"""

import numpy as np
import pytest

from incubator_mxnet_tpu import nd, ops
from incubator_mxnet_tpu.test_utils import check_numeric_gradient

R = np.random.RandomState


def _a(shape, seed=0, lo=-1.0, hi=1.0):
    return nd.array(R(seed).uniform(lo, hi, shape).astype(np.float32))


def _away(shape, seed=0, lo=0.2, hi=1.0):
    """Values in ±[lo, hi] — bounded away from 0 (relu/abs/sign kinks)."""
    r = R(seed)
    mag = r.uniform(lo, hi, shape)
    sgn = np.where(r.rand(*shape) < 0.5, -1.0, 1.0)
    return nd.array((mag * sgn).astype(np.float32))


def _distinct(shape, seed=0, scale=0.1):
    """Distinct values (max/min/sort/pool ties break finite differences)."""
    n = int(np.prod(shape))
    vals = (np.arange(n, dtype=np.float32) - n / 2) * scale
    return nd.array(R(seed).permutation(vals).reshape(shape))


def _spd(n, seed=0):
    m = R(seed).randn(n, n).astype(np.float32)
    return nd.array(m @ m.T + n * np.eye(n, dtype=np.float32))


def _ints(shape, hi, seed=0):
    return nd.array(R(seed).randint(0, hi, shape).astype(np.int32),
                    dtype="int32")


def _sumall(out):
    """Reduce an op output (array or list of arrays) to one scalar."""
    if isinstance(out, (list, tuple)):
        total = out[0].sum()
        for o in out[1:]:
            total = total + o.sum()
        return total
    return out.sum()


# --------------------------------------------------------------------- #
# differentiable ops: name -> thunk() -> (fn, inputs[, options])
# options: grad_nodes, rtol, atol, eps
# --------------------------------------------------------------------- #
GRAD_CASES = {
    # -- unary elementwise (smooth, or checked away from kinks) --------- #
    "abs": lambda: (nd.abs, [_away((2, 3))]),
    "arccos": lambda: (nd.arccos, [_a((2, 3), lo=-0.8, hi=0.8)]),
    "arccosh": lambda: (nd.arccosh, [_a((2, 3), lo=1.2, hi=2.0)]),
    "arcsin": lambda: (nd.arcsin, [_a((2, 3), lo=-0.8, hi=0.8)]),
    "arcsinh": lambda: (nd.arcsinh, [_a((2, 3))]),
    "arctan": lambda: (nd.arctan, [_a((2, 3))]),
    "arctanh": lambda: (nd.arctanh, [_a((2, 3), lo=-0.8, hi=0.8)]),
    "cbrt": lambda: (nd.cbrt, [_away((2, 3))]),
    "cos": lambda: (nd.cos, [_a((2, 3))]),
    "cosh": lambda: (nd.cosh, [_a((2, 3))]),
    "degrees": lambda: (nd.degrees, [_a((2, 3))]),
    "digamma": lambda: (nd.digamma, [_a((2, 3), lo=0.5, hi=2.0)]),
    "erf": lambda: (nd.erf, [_a((2, 3))]),
    "erfinv": lambda: (nd.erfinv, [_a((2, 3), lo=-0.8, hi=0.8)]),
    "exp": lambda: (nd.exp, [_a((2, 3))]),
    "expm1": lambda: (nd.expm1, [_a((2, 3))]),
    "gamma": lambda: (nd.gamma, [_a((2, 3), lo=0.5, hi=2.0)]),
    "gammaln": lambda: (nd.gammaln, [_a((2, 3), lo=0.5, hi=2.0)]),
    "gelu": lambda: (nd.gelu, [_a((2, 3))]),
    "hard_sigmoid": lambda: (nd.hard_sigmoid, [_a((2, 3))]),
    "identity": lambda: (nd.identity, [_a((2, 3))]),
    "log": lambda: (nd.log, [_a((2, 3), lo=0.2, hi=2.0)]),
    "log10": lambda: (nd.log10, [_a((2, 3), lo=0.2, hi=2.0)]),
    "log1p": lambda: (nd.log1p, [_a((2, 3), lo=-0.5, hi=2.0)]),
    "log2": lambda: (nd.log2, [_a((2, 3), lo=0.2, hi=2.0)]),
    "negative": lambda: (nd.negative, [_a((2, 3))]),
    "quadratic": lambda: (
        lambda x: nd.quadratic(x, a=0.3, b=-0.7, c=1.1), [_a((2, 3))]),
    "radians": lambda: (nd.radians, [_a((2, 3))]),
    "rcbrt": lambda: (nd.rcbrt, [_a((2, 3), lo=0.3, hi=1.5)]),
    "reciprocal": lambda: (nd.reciprocal, [_away((2, 3), lo=0.4)]),
    "relu": lambda: (nd.relu, [_away((2, 3))]),
    "rsqrt": lambda: (nd.rsqrt, [_a((2, 3), lo=0.3, hi=2.0)]),
    "sigmoid": lambda: (nd.sigmoid, [_a((2, 3))]),
    "sin": lambda: (nd.sin, [_a((2, 3))]),
    "sinh": lambda: (nd.sinh, [_a((2, 3))]),
    "smooth_l1": lambda: (
        lambda x: nd.smooth_l1(x, scalar=1.0), [_a((2, 3))]),
    "softsign": lambda: (nd.softsign, [_a((2, 3))]),
    "sqrt": lambda: (nd.sqrt, [_a((2, 3), lo=0.3, hi=2.0)]),
    "square": lambda: (nd.square, [_a((2, 3))]),
    "tan": lambda: (nd.tan, [_a((2, 3))]),
    "tanh": lambda: (nd.tanh, [_a((2, 3))]),
    "clip": lambda: (
        lambda x: nd.clip(x, a_min=-2.0, a_max=2.0), [_a((2, 3))]),
    "Cast": lambda: (
        lambda x: nd.Cast(x, dtype="float32"), [_a((2, 3))]),
    "amp_cast": lambda: (
        lambda x: nd.amp_cast(x, dtype="float32"), [_a((2, 3))]),
    "amp_multicast": lambda: (
        lambda a, b: _sumall(nd.amp_multicast(a, b, num_outputs=2)),
        [_a((2, 3)), _a((3,), seed=1)]),
    "Activation": lambda: (
        lambda x: nd.Activation(x, act_type="softrelu"), [_a((2, 3))]),
    "LeakyReLU": lambda: (
        lambda x: nd.LeakyReLU(x, act_type="leaky", slope=0.25),
        [_away((2, 3))]),
    "gradientmultiplier_scale1": None,  # placeholder, see CUSTOM_GRAD
    # -- scalar arith --------------------------------------------------- #
    "_plus_scalar": lambda: (
        lambda x: nd._plus_scalar(x, scalar=0.7), [_a((2, 3))]),
    "_minus_scalar": lambda: (
        lambda x: nd._minus_scalar(x, scalar=0.7), [_a((2, 3))]),
    "_rminus_scalar": lambda: (
        lambda x: nd._rminus_scalar(x, scalar=0.7), [_a((2, 3))]),
    "_mul_scalar": lambda: (
        lambda x: nd._mul_scalar(x, scalar=-1.3), [_a((2, 3))]),
    "_div_scalar": lambda: (
        lambda x: nd._div_scalar(x, scalar=1.7), [_a((2, 3))]),
    "_rdiv_scalar": lambda: (
        lambda x: nd._rdiv_scalar(x, scalar=1.7), [_away((2, 3), lo=0.5)]),
    "_power_scalar": lambda: (
        lambda x: nd._power_scalar(x, scalar=2.5),
        [_a((2, 3), lo=0.3, hi=1.5)]),
    "_rpower_scalar": lambda: (
        lambda x: nd._rpower_scalar(x, scalar=2.0), [_a((2, 3))]),
    "_maximum_scalar": lambda: (
        lambda x: nd._maximum_scalar(x, scalar=0.0), [_away((2, 3))]),
    "_minimum_scalar": lambda: (
        lambda x: nd._minimum_scalar(x, scalar=0.0), [_away((2, 3))]),
    "_mod_scalar": lambda: (
        lambda x: nd._mod_scalar(x, scalar=1.0),
        [_a((2, 3), lo=0.1, hi=0.9)]),
    "_rmod_scalar": lambda: (
        lambda x: nd._rmod_scalar(x, scalar=1.0),
        [_a((2, 3), lo=0.7, hi=0.9)]),
    "_slice_index": lambda: (
        lambda x: nd._slice_index(x, index=1), [_a((3, 4))]),
    # -- binary broadcast ----------------------------------------------- #
    "broadcast_add": lambda: (
        nd.broadcast_add, [_a((2, 3)), _a((1, 3), seed=1)]),
    "broadcast_sub": lambda: (
        nd.broadcast_sub, [_a((2, 3)), _a((1, 3), seed=1)]),
    "broadcast_mul": lambda: (
        nd.broadcast_mul, [_a((2, 3)), _a((1, 3), seed=1)]),
    "broadcast_div": lambda: (
        nd.broadcast_div, [_a((2, 3)), _away((1, 3), seed=1, lo=0.5)]),
    "broadcast_power": lambda: (
        nd.broadcast_power,
        [_a((2, 3), lo=0.3, hi=1.5), _a((1, 3), seed=1)]),
    "broadcast_hypot": lambda: (
        nd.broadcast_hypot, [_away((2, 3)), _away((1, 3), seed=1)]),
    "broadcast_maximum": lambda: (
        nd.broadcast_maximum, [_distinct((2, 3)), _distinct((1, 3), 1)]),
    "broadcast_minimum": lambda: (
        nd.broadcast_minimum, [_distinct((2, 3)), _distinct((1, 3), 1)]),
    "broadcast_mod": lambda: (
        nd.broadcast_mod,
        [_a((2, 3), lo=0.1, hi=0.9), nd.array(np.full((1, 3), 1.0,
                                                      np.float32))],
        {"grad_nodes": [0]}),
    "broadcast_to": lambda: (
        lambda x: nd.broadcast_to(x, shape=(4, 3)), [_a((1, 3))]),
    "broadcast_axis": lambda: (
        lambda x: nd.broadcast_axis(x, axis=0, size=4), [_a((1, 3))]),
    "broadcast_like": lambda: (
        lambda x, y: nd.broadcast_like(x, y),
        [_a((1, 3)), _a((4, 3), seed=1)], {"grad_nodes": [0]}),
    # -- reductions ----------------------------------------------------- #
    "sum": lambda: (lambda x: nd.sum(x, axis=1), [_a((3, 4))]),
    "nansum": lambda: (lambda x: nd.nansum(x, axis=1), [_a((3, 4))]),
    "mean": lambda: (lambda x: nd.mean(x, axis=0), [_a((3, 4))]),
    "prod": lambda: (
        lambda x: nd.prod(x, axis=1), [_away((2, 3), lo=0.5)]),
    "nanprod": lambda: (
        lambda x: nd.nanprod(x, axis=1), [_away((2, 3), lo=0.5)]),
    "max": lambda: (lambda x: nd.max(x, axis=1), [_distinct((3, 4))]),
    "min": lambda: (lambda x: nd.min(x, axis=1), [_distinct((3, 4))]),
    "norm": lambda: (
        lambda x: nd.norm(x, ord=2, axis=1), [_away((2, 3))]),
    "logsumexp": lambda: (
        lambda x: nd.logsumexp(x, axis=-1), [_a((2, 3))]),
    "moments": lambda: (
        lambda x: _sumall(nd.moments(x, axes=(0,))), [_a((3, 4))]),
    "cumsum": lambda: (lambda x: nd.cumsum(x, axis=1), [_a((2, 4))]),
    "cumprod": lambda: (
        lambda x: nd.cumprod(x, axis=1), [_away((2, 3), lo=0.5)]),
    "softmax": lambda: (lambda x: nd.softmax(x, axis=-1), [_a((2, 4))]),
    "softmin": lambda: (lambda x: nd.softmin(x, axis=-1), [_a((2, 4))]),
    "log_softmax": lambda: (
        lambda x: nd.log_softmax(x, axis=-1), [_a((2, 4))]),
    "masked_softmax": lambda: (
        lambda x: nd.masked_softmax(
            x, mask=nd.array(np.array([[1, 1, 0, 1]] * 2, np.float32))),
        [_a((2, 4))]),
    "SoftmaxActivation": lambda: (nd.SoftmaxActivation, [_a((2, 4))]),
    "softmax_cross_entropy": lambda: (
        lambda x: nd.softmax_cross_entropy(x, nd.array([0.0, 2.0])),
        [_a((2, 4))]),
    "div_sqrt_dim": lambda: (nd.div_sqrt_dim, [_a((2, 4))]),
    "logical_not_placeholder": None,
    # -- shape / layout (linear) ---------------------------------------- #
    "reshape": lambda: (
        lambda x: nd.reshape(x, shape=(3, 2)), [_a((2, 3))]),
    "reshape_like": lambda: (
        lambda x, y: nd.reshape_like(x, y),
        [_a((2, 3)), _a((3, 2), seed=1)], {"grad_nodes": [0]}),
    "flatten": lambda: (nd.flatten, [_a((2, 3, 2))]),
    "transpose": lambda: (
        lambda x: nd.transpose(x, axes=(1, 0)), [_a((2, 3))]),
    "swapaxes": lambda: (
        lambda x: nd.swapaxes(x, dim1=0, dim2=2), [_a((2, 3, 2))]),
    "expand_dims": lambda: (
        lambda x: nd.expand_dims(x, axis=1), [_a((2, 3))]),
    "squeeze": lambda: (
        lambda x: nd.squeeze(x, axis=1), [_a((2, 1, 3))]),
    "flip": lambda: (lambda x: nd.flip(x, axis=1), [_a((2, 3))]),
    "tile": lambda: (lambda x: nd.tile(x, reps=(2, 2)), [_a((2, 3))]),
    "repeat": lambda: (
        lambda x: nd.repeat(x, repeats=2, axis=1), [_a((2, 3))]),
    "pad": lambda: (
        lambda x: nd.pad(x, mode="constant",
                         pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
        [_a((1, 1, 3, 3))]),
    "slice": lambda: (
        lambda x: nd.slice(x, begin=(0, 1), end=(2, 3)), [_a((3, 4))]),
    "slice_axis": lambda: (
        lambda x: nd.slice_axis(x, axis=1, begin=1, end=3), [_a((3, 4))]),
    "slice_like": lambda: (
        lambda x, y: nd.slice_like(x, y),
        [_a((3, 4)), _a((2, 3), seed=1)], {"grad_nodes": [0]}),
    "Crop": lambda: (
        lambda x: nd.Crop(x, num_args=1, offset=(1, 1), h_w=(2, 2)),
        [_a((1, 1, 4, 4))]),
    "concat": lambda: (
        lambda a, b: nd.concat(a, b, dim=1),
        [_a((2, 3)), _a((2, 2), seed=1)]),
    "stack": lambda: (
        lambda a, b: nd.stack(a, b, axis=0),
        [_a((2, 3)), _a((2, 3), seed=1)]),
    "split": lambda: (
        lambda x: _sumall(nd.split(x, num_outputs=2, axis=1)),
        [_a((2, 4))]),
    "split_v2": lambda: (
        lambda x: _sumall(nd.split_v2(x, indices_or_sections=2, axis=1)),
        [_a((2, 4))]),
    "meshgrid": lambda: (
        lambda a, b: _sumall(nd.meshgrid(a, b)),
        [_a((3,)), _a((2,), seed=1)]),
    "diag": lambda: (nd.diag, [_a((3, 3))]),
    "tril": lambda: (nd.tril, [_a((3, 3))]),
    "triu": lambda: (nd.triu, [_a((3, 3))]),
    "depth_to_space": lambda: (
        lambda x: nd.depth_to_space(x, block_size=2), [_a((1, 4, 2, 2))]),
    "space_to_depth": lambda: (
        lambda x: nd.space_to_depth(x, block_size=2), [_a((1, 1, 4, 4))]),
    "im2col": lambda: (
        lambda x: nd.im2col(x, kernel=(2, 2), stride=(1, 1),
                            dilate=(1, 1), pad=(0, 0)),
        [_a((1, 2, 4, 4))]),
    "col2im": lambda: (
        lambda x: nd.col2im(x, output_size=(4, 4), kernel=(2, 2),
                            stride=(1, 1), dilate=(1, 1), pad=(0, 0)),
        [_a((1, 8, 9))]),
    "UpSampling": lambda: (
        lambda x: nd.UpSampling(x, scale=2, sample_type="nearest"),
        [_a((1, 1, 3, 3))]),
    # -- indexing / gather (wrt float data) ----------------------------- #
    "take": lambda: (
        lambda x: nd.take(x, _ints((3,), 3, seed=5), axis=0),
        [_a((3, 4))]),
    "batch_take": lambda: (
        lambda x: nd.batch_take(x, _ints((3,), 4, seed=5)), [_a((3, 4))]),
    "pick": lambda: (
        lambda x: nd.pick(x, _ints((3,), 4, seed=5), axis=1),
        [_a((3, 4))]),
    "gather_nd": lambda: (
        lambda x: nd.gather_nd(
            x, nd.array(np.array([[0, 2], [1, 0]], np.int32),
                        dtype="int32")),
        [_a((3, 4))]),
    "scatter_nd": lambda: (
        lambda x: nd.scatter_nd(
            x, nd.array(np.array([[0, 2]], np.int32), dtype="int32"),
            shape=(4,)),
        [_a((2,))]),
    "boolean_mask": lambda: (
        lambda x: nd.boolean_mask(
            x, nd.array(np.array([1, 0, 1], np.int32), dtype="int32")),
        [_a((3, 4))]),
    "one_hot_placeholder": None,
    "where": lambda: (
        lambda x, y: nd.where(
            nd.array(np.array([[1, 0], [0, 1]], np.float32)), x, y),
        [_a((2, 2)), _a((2, 2), seed=1)]),
    "index_add": lambda: (
        lambda old, new: nd.index_add(
            old, _ints((2,), 3, seed=7), new),
        [_a((3, 4)), _a((2, 4), seed=1)]),
    "index_copy": lambda: (
        lambda old, new: nd.index_copy(
            old, nd.array(np.array([0, 2], np.int32), dtype="int32"), new),
        [_a((3, 4)), _a((2, 4), seed=1)]),
    "choose_element_0index": lambda: (
        lambda x: nd.choose_element_0index(x, nd.array([0.0, 2.0, 1.0])),
        [_a((3, 4))]),
    "fill_element_0index": lambda: (
        lambda x, v: nd.fill_element_0index(
            x, v, nd.array([0.0, 2.0, 1.0])),
        [_a((3, 4)), _a((3,), seed=1)]),
    "SequenceLast": lambda: (
        lambda x: nd.SequenceLast(
            x, sequence_length=nd.array([2.0, 3.0]),
            use_sequence_length=True),
        [_a((3, 2, 4))]),
    "SequenceMask": lambda: (
        lambda x: nd.SequenceMask(
            x, sequence_length=nd.array([2.0, 3.0]),
            use_sequence_length=True, value=0.0),
        [_a((3, 2, 4))]),
    "SequenceReverse": lambda: (
        lambda x: nd.SequenceReverse(
            x, sequence_length=nd.array([2.0, 3.0]),
            use_sequence_length=True),
        [_a((3, 2, 4))]),
    "sort": lambda: (
        lambda x: nd.sort(x, axis=-1), [_distinct((2, 4))]),
    "topk": lambda: (
        lambda x: nd.topk(x, k=2, ret_typ="value"), [_distinct((2, 4))]),
    # -- matmul / linalg ------------------------------------------------ #
    "dot": lambda: (nd.dot, [_a((2, 3)), _a((3, 4), seed=1)]),
    "batch_dot": lambda: (
        nd.batch_dot, [_a((2, 2, 3)), _a((2, 3, 2), seed=1)]),
    "khatri_rao": lambda: (
        nd.khatri_rao, [_a((2, 3)), _a((4, 3), seed=1)]),
    "add_n": lambda: (
        nd.add_n, [_a((2, 3)), _a((2, 3), seed=1), _a((2, 3), seed=2)]),
    "linalg_gemm": lambda: (
        lambda a, b, c: nd.linalg_gemm(a, b, c, alpha=1.3, beta=0.7),
        [_a((2, 3)), _a((3, 2), seed=1), _a((2, 2), seed=2)]),
    "linalg_gemm2": lambda: (
        lambda a, b: nd.linalg_gemm2(a, b, alpha=1.3),
        [_a((2, 3)), _a((3, 2), seed=1)]),
    "linalg_syrk": lambda: (
        lambda a: nd.linalg_syrk(a, alpha=1.1), [_a((2, 3))]),
    "linalg_trmm": lambda: (
        lambda a, b: nd.linalg_trmm(a, b),
        [_spd(3), _a((3, 2), seed=1)], {"rtol": 3e-2}),
    "linalg_trsm": lambda: (
        lambda a, b: nd.linalg_trsm(a, b),
        [_spd(3), _a((3, 2), seed=1)], {"rtol": 3e-2}),
    "linalg_potrf": lambda: (
        nd.linalg_potrf, [_spd(3)], {"rtol": 3e-2}),
    "linalg_potri": lambda: (
        nd.linalg_potri, [_spd(3)], {"rtol": 5e-2, "atol": 5e-3}),
    "linalg_det": lambda: (nd.linalg_det, [_spd(3)], {"rtol": 3e-2}),
    "linalg_slogdet": lambda: (
        lambda a: nd.linalg_slogdet(a)[1], [_spd(3)], {"rtol": 3e-2}),
    "linalg_inverse": lambda: (
        nd.linalg_inverse, [_spd(3)], {"rtol": 5e-2, "atol": 5e-3}),
    "linalg_sumlogdiag": lambda: (
        nd.linalg_sumlogdiag, [_spd(3)], {"rtol": 3e-2}),
    "linalg_extractdiag": lambda: (nd.linalg_extractdiag, [_a((3, 3))]),
    "linalg_extracttrian": lambda: (nd.linalg_extracttrian, [_a((3, 3))]),
    "linalg_makediag": lambda: (nd.linalg_makediag, [_a((3,))]),
    "linalg_maketrian": lambda: (nd.linalg_maketrian, [_a((6,))]),
    # decompositions: heads chosen invariant to the sign/ordering
    # conventions (fixed projections; U*U for eigenvectors; singular
    # values alone for SVD) so finite differences are well-defined
    "linalg_gelqf": lambda: (
        lambda a: (lambda LQ: LQ[0].sum()
                   + (LQ[1] * _a((3, 4), seed=9)).sum())(
            nd.linalg_gelqf(a)),
        [_a((3, 4), lo=-0.5, hi=0.5)], {"rtol": 3e-2, "atol": 3e-3}),
    "linalg_syevd": lambda: (
        lambda a: (lambda Ul: Ul[1].sum()
                   + (Ul[0] * Ul[0] * _a((3, 3), seed=9)).sum())(
            nd.linalg_syevd(a)),
        [_spd(3)], {"rtol": 3e-2, "atol": 3e-3}),
    "linalg_gesvd": lambda: (
        lambda a: nd.linalg_gesvd(a)[1].sum(),
        [_a((3, 4), lo=-0.5, hi=0.5)], {"rtol": 3e-2, "atol": 3e-3}),
    # -- neural layers -------------------------------------------------- #
    "FullyConnected": lambda: (
        lambda x, w, b: nd.FullyConnected(x, w, b, num_hidden=3),
        [_a((2, 4)), _a((3, 4), seed=1), _a((3,), seed=2)]),
    "Convolution": lambda: (
        lambda x, w, b: nd.Convolution(x, w, b, kernel=(3, 3),
                                       num_filter=3),
        [_a((1, 2, 5, 5)), _a((3, 2, 3, 3), seed=1), _a((3,), seed=2)],
        {"rtol": 5e-2, "atol": 5e-3}),
    "Deconvolution": lambda: (
        lambda x, w: nd.Deconvolution(x, w, kernel=(3, 3), num_filter=2,
                                      no_bias=True),
        [_a((1, 3, 4, 4)), _a((3, 2, 3, 3), seed=1)], {"rtol": 3e-2}),
    "Pooling": lambda: (
        lambda x: nd.Pooling(x, kernel=(2, 2), pool_type="avg",
                             stride=(1, 1)),
        [_a((1, 2, 4, 4))]),
    "AdaptiveAvgPooling2D": lambda: (
        lambda x: nd.AdaptiveAvgPooling2D(x, output_size=2),
        [_a((1, 2, 4, 4))]),
    "LRN": lambda: (
        lambda x: nd.LRN(x, nsize=3), [_a((1, 4, 3, 3))]),
    "LayerNorm": lambda: (
        lambda x, g, b: nd.LayerNorm(x, g, b),
        [_a((2, 4)), _a((4,), seed=1, lo=0.5, hi=1.5),
         _a((4,), seed=2)]),
    "GroupNorm": lambda: (
        lambda x, g, b: nd.GroupNorm(x, g, b, num_groups=2),
        [_a((2, 4, 3)), _a((4,), seed=1, lo=0.5, hi=1.5),
         _a((4,), seed=2)], {"rtol": 3e-2}),
    "InstanceNorm": lambda: (
        lambda x, g, b: nd.InstanceNorm(x, g, b),
        [_a((2, 3, 4)), _a((3,), seed=1, lo=0.5, hi=1.5),
         _a((3,), seed=2)], {"rtol": 3e-2}),
    # use_global_stats: the harness evaluates numeric differences under
    # autograd.pause(), where a training-aware BatchNorm would switch to
    # the inference path and diverge from the analytic (recorded)
    # forward; global-stats mode is identical in both and still checks
    # the full (x - mean)/sqrt(var+eps)*gamma + beta wiring
    "BatchNorm": lambda: (
        lambda x, g, b, mm, mv: (nd.BatchNorm(
            x, g, b, mm, mv, fix_gamma=False, use_global_stats=True)[0]
            * _a((4, 3), seed=9)).sum(),
        [_a((4, 3)), _a((3,), seed=1, lo=0.5, hi=1.5), _a((3,), seed=2),
         _a((3,), seed=3), _a((3,), seed=4, lo=0.5, hi=1.5)],
        {"grad_nodes": [0, 1, 2], "rtol": 3e-2, "atol": 3e-3}),
    "L2Normalization": lambda: (
        nd.L2Normalization, [_away((2, 4))]),
    "Embedding": lambda: (
        lambda w: nd.Embedding(_ints((3,), 5, seed=5), w, input_dim=5,
                               output_dim=4),
        [_a((5, 4))]),
    "Dropout_placeholder": None,
    # fused packed-parameter RNN: lstm gate packing 4*(I*H + H*H + 2H)
    "RNN": lambda: (
        lambda d, p: (lambda o: o[0] if isinstance(o, list) else o)(
            nd.RNN(d, p, nd.array(np.zeros((1, 2, 4), np.float32)),
                   nd.array(np.zeros((1, 2, 4), np.float32)),
                   state_size=4, num_layers=1, mode="lstm")),
        [_a((3, 2, 3), lo=-0.5, hi=0.5),
         _a((4 * (3 * 4 + 4 * 4 + 2 * 4),), seed=1, lo=-0.3, hi=0.3)],
        {"rtol": 3e-2, "atol": 3e-3}),
    "CTCLoss": lambda: (
        lambda x: nd.CTCLoss(x, nd.array(np.array([[1, 2], [2, 1]],
                                                  np.float32))),
        [_a((4, 2, 4))], {"rtol": 3e-2, "atol": 3e-3}),
    "BilinearResize2D": lambda: (
        lambda x: nd.BilinearResize2D(x, height=4, width=4),
        [_a((1, 1, 3, 3))]),
    "GridGenerator": lambda: (
        lambda x: nd.GridGenerator(x, transform_type="affine",
                                   target_shape=(4, 4)),
        [_a((1, 6))]),
    "BilinearSampler": lambda: (
        lambda x, g: nd.BilinearSampler(x, g),
        [_a((1, 1, 4, 4)),
         _a((1, 2, 3, 3), seed=1, lo=-0.6, hi=0.6)],
        {"rtol": 5e-2, "atol": 5e-3}),
    "SpatialTransformer": lambda: (
        lambda x, loc: nd.SpatialTransformer(
            x, loc, target_shape=(4, 4), transform_type="affine",
            sampler_type="bilinear"),
        [_a((1, 1, 4, 4)),
         # theta chosen so no bilinear sample point sits near an
         # integer source coordinate (finite differences would cross
         # the sampling kink): x_src/y_src land 0.15+ from integers
         nd.array(np.array([[0.61, 0.02, 0.05, -0.03, 0.57, 0.03]],
                           np.float32))],
        {"rtol": 5e-2, "atol": 5e-3}),
    "ROIAlign": lambda: (
        lambda x: nd.ROIAlign(
            x, nd.array(np.array([[0, 0.6, 0.6, 3.3, 3.3]], np.float32)),
            pooled_size=(2, 2), spatial_scale=1.0, sample_ratio=2),
        [_a((1, 1, 6, 6))], {"rtol": 5e-2, "atol": 5e-3}),
    "ROIPooling": lambda: (
        lambda x: nd.ROIPooling(
            x, nd.array(np.array([[0, 0, 0, 3, 3]], np.float32)),
            pooled_size=(2, 2), spatial_scale=1.0),
        [_distinct((1, 1, 6, 6))], {"rtol": 3e-2}),
    "Correlation": lambda: (
        lambda a, b: nd.Correlation(a, b, kernel_size=1,
                                    max_displacement=1),
        [_a((1, 1, 4, 4)), _a((1, 1, 4, 4), seed=1)], {"rtol": 3e-2}),
    "DeformableConvolution": lambda: (
        lambda x, off, w: nd.DeformableConvolution(
            x, off, w, kernel=(3, 3), num_filter=2, no_bias=True),
        [_a((1, 2, 5, 5)),
         _a((1, 18, 3, 3), seed=1, lo=0.1, hi=0.35),
         _a((2, 2, 3, 3), seed=2)],
        {"rtol": 5e-2, "atol": 5e-3}),
    "ModulatedDeformableConvolution": lambda: (
        lambda x, off, m, w: nd.ModulatedDeformableConvolution(
            x, off, m, w, kernel=(3, 3), num_filter=2, no_bias=True),
        [_a((1, 2, 5, 5)),
         _a((1, 18, 3, 3), seed=1, lo=0.1, hi=0.35),
         _a((1, 9, 3, 3), seed=3, lo=0.3, hi=0.9),
         _a((2, 2, 3, 3), seed=2)],
        {"rtol": 5e-2, "atol": 5e-3}),
    # -- attention ------------------------------------------------------ #
    "scaled_dot_product_attention": lambda: (
        lambda q, k, v: nd.scaled_dot_product_attention(q, k, v),
        [_a((1, 3, 2, 4)), _a((1, 3, 2, 4), seed=1),
         _a((1, 3, 2, 4), seed=2)], {"rtol": 3e-2}),
    "interleaved_matmul_selfatt_qk": lambda: (
        lambda qkv: nd.interleaved_matmul_selfatt_qk(qkv, heads=2),
        [_a((3, 2, 24))], {"rtol": 3e-2}),
    "interleaved_matmul_selfatt_valatt": lambda: (
        lambda qkv, att: nd.interleaved_matmul_selfatt_valatt(
            qkv, att, heads=2),
        [_a((3, 2, 24)), _a((4, 3, 3), seed=1)], {"rtol": 3e-2}),
    "interleaved_matmul_encdec_qk": lambda: (
        lambda q, kv: nd.interleaved_matmul_encdec_qk(q, kv, heads=2),
        [_a((3, 2, 8)), _a((3, 2, 16), seed=1)], {"rtol": 3e-2}),
    "interleaved_matmul_encdec_valatt": lambda: (
        lambda kv, att: nd.interleaved_matmul_encdec_valatt(
            kv, att, heads=2),
        [_a((3, 2, 16)), _a((4, 3, 3), seed=1)], {"rtol": 3e-2}),
    "sldwin_atten_score": lambda: (
        lambda q, k: nd.sldwin_atten_score(q, k, 1, num_heads=2, w=2),
        [_a((2, 6, 8)), _a((2, 6, 8), seed=1)], {"rtol": 3e-2}),
    "sldwin_atten_context": lambda: (
        lambda s, v: nd.sldwin_atten_context(s, v, 1, num_heads=2, w=2),
        [_a((4, 6, 6)), _a((2, 6, 8), seed=1)], {"rtol": 3e-2}),
    # -- misc ----------------------------------------------------------- #
    "count_sketch": lambda: (
        lambda x: nd.count_sketch(
            x, nd.array(R(5).randint(0, 4, 8).astype(np.float32)),
            nd.array(R(6).choice([-1.0, 1.0], 8).astype(np.float32)),
            out_dim=4),
        [_a((2, 8))]),
    "fft": lambda: (
        lambda x: nd.fft(x, compute_size=4), [_a((2, 4))]),
    "ifft": lambda: (
        lambda x: nd.ifft(x, compute_size=4), [_a((2, 8))]),
    "box_decode": lambda: (
        lambda x, a: nd.box_decode(x, a),
        [_a((1, 2, 4), lo=-0.2, hi=0.2),
         nd.array(np.array([[[0.1, 0.1, 0.4, 0.4],
                             [0.5, 0.5, 0.9, 0.9]]], np.float32))],
        {"grad_nodes": [0], "rtol": 3e-2}),
    "box_iou": lambda: (
        lambda a, b: nd.box_iou(a, b),
        [nd.array(np.array([[0.1, 0.1, 0.6, 0.6]], np.float32)),
         nd.array(np.array([[0.3, 0.3, 0.9, 0.9]], np.float32))],
        {"rtol": 5e-2, "atol": 5e-3}),
    "hawkes_ll": lambda: (
        lambda lda, alpha, beta: _sumall(nd.hawkes_ll(
            lda, alpha, beta, nd.zeros((1, 1)),
            nd.array(np.array([[1.0, 0.5, 0.8]], np.float32)),
            nd.array(np.zeros((1, 3), np.float32)),
            nd.array(np.array([3], np.int32), dtype="int32"), 4.0)),
        [nd.array([0.5]), nd.array([0.2]), nd.array([1.0])],
        {"rtol": 3e-2}),
}
# drop documentation placeholders (classified in other buckets)
GRAD_CASES = {k: v for k, v in GRAD_CASES.items() if v is not None}

# ops whose outputs are integer/boolean/assignment results,
# value-independent of the float inputs, or zero-gradient by definition
NONDIFF = {
    # comparisons / logical / boolean outputs
    "_equal_scalar": "boolean output", "_not_equal_scalar": "boolean",
    "_greater_scalar": "boolean", "_greater_equal_scalar": "boolean",
    "_lesser_scalar": "boolean", "_lesser_equal_scalar": "boolean",
    "broadcast_equal": "boolean", "broadcast_not_equal": "boolean",
    "broadcast_greater": "boolean", "broadcast_greater_equal": "boolean",
    "broadcast_lesser": "boolean", "broadcast_lesser_equal": "boolean",
    "broadcast_logical_and": "boolean", "broadcast_logical_or": "boolean",
    "broadcast_logical_xor": "boolean", "logical_not": "boolean",
    "isfinite": "boolean", "isinf": "boolean", "isnan": "boolean",
    "allclose": "boolean", "all_finite": "boolean scalar",
    "multi_all_finite": "boolean scalar",
    # integer / index outputs
    "argmax": "index output", "argmin": "index output",
    "argsort": "index output", "argmax_channel": "index output",
    "histogram": "integer counts", "one_hot": "indices input",
    "ravel_multi_index": "integer", "unravel_index": "integer",
    "shape_array": "shape metadata", "size_array": "size metadata",
    "index_array": "value-independent indices",
    # value-independent outputs
    "zeros_like": "constant output", "ones_like": "constant output",
    "full_like": "constant output", "arange_like": "value-independent",
    "MultiBoxPrior": "anchors depend only on shape",
    # piecewise-constant (zero gradient a.e.)
    "ceil": "zero gradient a.e.", "floor": "zero gradient a.e.",
    "fix": "zero gradient a.e.", "rint": "zero gradient a.e.",
    "round": "zero gradient a.e.", "trunc": "zero gradient a.e.",
    "sign": "zero gradient a.e.",
    # assignment / matching / NMS logic
    "box_nms": "NMS selection logic",
    "bipartite_matching": "assignment indices",
    "MultiBoxDetection": "NMS + decode selection",
    "MultiBoxTarget": "target assignment",
    "Proposal": "NMS proposal selection",
    "mrcnn_mask_target": "target assignment",
    "box_encode": "matching-driven gather",
    "sldwin_atten_mask_like": "boolean band mask",
    # quantized integer path
    "quantize": "int8/uint8 output", "quantize_v2": "int8 output",
    "dequantize": "int8 input", "requantize": "int8 path",
    "quantized_conv": "int8 path",
    "quantized_fully_connected": "int8 path",
    # optimizer update kernels: applied outside the differentiated
    # graph; trajectory-tested in tests/test_optimizer.py
    "adadelta_update": "optimizer kernel",
    "adagrad_update": "optimizer kernel", "adam_update": "optimizer",
    "adamw_update": "optimizer", "ftml_update": "optimizer",
    "ftrl_update": "optimizer", "group_adagrad_update": "optimizer",
    "lamb_update_phase1": "optimizer", "lamb_update_phase2": "optimizer",
    "mp_adam_update": "optimizer", "mp_adamw_update": "optimizer",
    "mp_nag_mom_update": "optimizer", "mp_sgd_mom_update": "optimizer",
    "mp_sgd_update": "optimizer", "multi_lars": "optimizer",
    "multi_mp_sgd_mom_update": "optimizer",
    "multi_mp_sgd_update": "optimizer",
    "multi_sgd_mom_update": "optimizer", "multi_sgd_update": "optimizer",
    "multi_sum_sq": "optimizer-infra reduction",
    "nag_mom_update": "optimizer",
    "preloaded_multi_sgd_update": "optimizer",
    "preloaded_multi_sgd_mom_update": "optimizer",
    "preloaded_multi_mp_sgd_update": "optimizer",
    "preloaded_multi_mp_sgd_mom_update": "optimizer",
    "rmsprop_update": "optimizer",
    "rmspropalex_update": "optimizer", "sgd_mom_update": "optimizer",
    "sgd_update": "optimizer", "signsgd_update": "optimizer",
    "signum_update": "optimizer",
}

# training heads: forward is a pass-through, backward injects the loss
# gradient by design — numeric diff of the forward cannot agree
# (reference: src/operator/regression_output*.cc, softmax_output.cc)
CUSTOM_GRAD = {
    "SoftmaxOutput": "backward = (softmax - label)",
    "LinearRegressionOutput": "backward = data - label",
    "LogisticRegressionOutput": "backward = sigmoid(data) - label",
    "MAERegressionOutput": "backward = sign(data - label)",
    "SVMOutput": "backward = hinge subgradient",
    "make_loss": "forward identity, backward grad_scale",
    "BlockGrad": "gradient barrier (zero by definition)",
    "gradientmultiplier": "backward scaled by `scalar` by design",
}

# stochastic samplers checked at the DISTRIBUTION level instead of by
# numeric gradient (reference idiom: tests/python/unittest/test_random.py
# verifies sample moments against analytic ones under a fixed seed):
# name -> (thunk() -> samples NDArray, analytic mean, analytic variance)
_N_SAMPLES = 200_000
DIST_CHECK = {
    "random_normal": (
        lambda: nd.random_normal(loc=1.5, scale=2.0, shape=(_N_SAMPLES,)),
        1.5, 4.0),
    "random_uniform": (
        lambda: nd.random_uniform(low=-1.0, high=3.0, shape=(_N_SAMPLES,)),
        1.0, 16.0 / 12.0),
    "random_gamma": (
        # mean = alpha*beta, var = alpha*beta^2 (MXNet's beta is scale)
        lambda: nd.random_gamma(alpha=3.0, beta=0.5, shape=(_N_SAMPLES,)),
        1.5, 0.75),
}

# differentiable but excluded here, with reasons
SKIP = {
    "Dropout": "stochastic mask; parity-tested in tests/test_nn_ops.py",
    "flash_attention_packed": "Pallas-kernel-only op (raises where no "
                              "kernel runs); gradient parity in "
                              "tests/test_pallas_attention.py",
    "shuffle": "random permutation",
    "random_bernoulli": "sampler", "random_exponential": "sampler",
    "random_generalized_negative_binomial": "sampler",
    "random_laplace": "sampler", "random_negative_binomial": "sampler",
    "random_poisson": "sampler",
    "random_randint": "sampler", "random_randn": "sampler",
    "sample_multinomial": "sampler",
    "sample_normal": "sampler", "sample_uniform": "sampler",
    "sample_gamma": "sampler", "sample_exponential": "sampler",
    "sample_poisson": "sampler", "sample_negative_binomial": "sampler",
    "sample_generalized_negative_binomial": "sampler",
}


def test_registry_fully_classified():
    """Every registered op is in exactly one bucket; none unclassified."""
    registry = set(ops.list_all_ops())
    buckets = {"GRAD_CASES": set(GRAD_CASES), "NONDIFF": set(NONDIFF),
               "CUSTOM_GRAD": set(CUSTOM_GRAD), "SKIP": set(SKIP),
               "DIST_CHECK": set(DIST_CHECK)}
    classified = set().union(*buckets.values())
    missing = registry - classified
    assert not missing, f"unclassified ops: {sorted(missing)}"
    stale = classified - registry
    assert not stale, f"classified but unregistered: {sorted(stale)}"
    for a in buckets:
        for b in buckets:
            if a < b:
                dup = buckets[a] & buckets[b]
                assert not dup, f"{sorted(dup)} in both {a} and {b}"


@pytest.mark.parametrize("name", [
    # random_gamma's moment check costs 9 s (round-11 tier-1 budget
    # repair) — stage_unit still runs it
    pytest.param(n, marks=pytest.mark.slow) if n == "random_gamma"
    else n
    for n in sorted(DIST_CHECK)])
def test_sampler_distribution(name):
    """Moment check under a fixed seed: sample mean/variance within 5
    standard errors of the analytic moments (so the check is sharp but
    seed-stable), plus a determinism replay of the seeded stream."""
    import incubator_mxnet_tpu as mx

    thunk, mean, var = DIST_CHECK[name]
    mx.random.seed(1234)
    s = thunk().asnumpy().astype(np.float64)
    n = s.size
    se_mean = np.sqrt(var / n)
    assert abs(s.mean() - mean) < 5 * se_mean, \
        f"{name}: sample mean {s.mean():.4f} vs analytic {mean}"
    # SE of the sample variance ~ var * sqrt(2/(n-1)) for light-tailed
    # distributions; gamma's excess kurtosis widens it, folded into 5 SE
    kurt_margin = 5 * var * np.sqrt(2.0 / (n - 1)) * 3.0
    assert abs(s.var() - var) < kurt_margin, \
        f"{name}: sample var {s.var():.4f} vs analytic {var}"
    mx.random.seed(1234)
    np.testing.assert_array_equal(thunk().asnumpy(), s.astype(np.float32))


# multi-input kernels whose finite-difference sweeps take 30s+ each on
# the 8-virtual-device CPU mesh: still covered, but outside the tier-1
# `-m 'not slow'` budget (ci/run.sh stage_unit runs the full suite)
_SLOW_GRAD = {"RNN", "DeformableConvolution",
              "ModulatedDeformableConvolution",
              # 12s on the tier-1 budget box (round-10 --durations
              # profile); ci stage_unit still runs it
              "CTCLoss",
              # 11s (round-11 profile); stage_unit still runs it
              "ROIAlign"}


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.slow) if n in _SLOW_GRAD else n
    for n in sorted(GRAD_CASES)])
def test_numeric_gradient(name):
    case = GRAD_CASES[name]()
    fn, inputs = case[0], case[1]
    opts = dict(case[2]) if len(case) > 2 else {}
    check_numeric_gradient(fn, inputs,
                           grad_nodes=opts.get("grad_nodes"),
                           eps=opts.get("eps", 1e-3),
                           rtol=opts.get("rtol", 1e-2),
                           atol=opts.get("atol", 1e-3))
