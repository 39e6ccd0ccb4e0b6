"""Jaxpr pattern-matching fusion pass: megakernels across op boundaries.

PR 8's kernels fuse within one op; this pass (the FlashFuser direction
from PAPERS.md) walks a whole captured step's jaxpr and rewrites
eligible multi-op subgraphs to the block-fused Pallas kernels in
:mod:`.fused_kernels` — so ``nn.LayerNorm``-heavy models get megakernels
with zero source changes.  Patterns matched:

=================== =======================================================
``layer_norm``       the XLA layernorm soup (mean / ``_var`` jit / rsqrt /
                     affine) → :func:`fused_kernels.fused_layer_norm`
``residual_ln``      residual add feeding that soup, add consumed only by
                     it (post-LN transformers) → fused residual+LN kernel
``ln_matmul``        the soup's output feeding a single matmul (+bias)
                     (pre-LN qkv/mlp projections) →
                     :func:`fused_kernels.fused_ln_matmul`
``matmul_bias_gelu`` matmul + bias + gelu (tanh or erf form) →
                     :func:`fused_kernels.fused_matmul_bias_gelu`
``attention_block``  qk-matmul + scale (+ causal mask) + softmax +
                     pv-matmul → :func:`fused_kernels.fused_attention_block`
=================== =======================================================

Eligibility is structural: a subgraph is rewritten only when every
interior value is consumed inside the cluster (the cluster is *closed*
except for its single output).  Captured step jaxprs are post-AD — the
tape's backward re-traces the forward per-op, so forward clusters are
closed and replaceable while the backward's recompute copy (whose
interiors feed transposes) is left alone.

Dispatch follows ``device.pallas_dispatch``: on a TPU, in a program
Mosaic can lower, the cluster call runs the Pallas kernel (a kernel the
compiler refuses fails the program); elsewhere it runs an inline XLA
reference that mirrors the matched soup (reason ``not_tpu``, or
``gspmd_mesh`` on a TPU under a multi-device GSPMD mesh — CPU timing
and parity are unchanged, interpret mode is never on the rewritten
path).  ``PT_FUSION_PASS=0`` kills the pass;
``PT_FUSION_DISABLE=pat1,pat2`` opts out individual patterns.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.extend import core as jcore
from jax._src import source_info_util as _source_info

from ..framework import device as _device

__all__ = [
    "PATTERNS", "wrap", "match_jaxpr", "match_report", "count_patterns",
    "fusion_enabled", "disabled_patterns", "summary", "reset_stats",
]

PATTERNS = ("attention_block", "matmul_bias_gelu", "ln_matmul",
            "residual_ln", "layer_norm")

_FALSY = {"0", "false", "no", "off"}

_SQRT_HALF = 0.7071067811865476
_TANH_COEF = 0.7978845608028654
_TANH_CUBIC = 0.044715


def fusion_enabled() -> bool:
    return os.environ.get(
        "PT_FUSION_PASS", "1").strip().lower() not in _FALSY


def disabled_patterns() -> set:
    raw = os.environ.get("PT_FUSION_DISABLE", "")
    return {t.strip() for t in raw.split(",") if t.strip()}


# ---------------------------------------------------------------------------
# stats + telemetry
# ---------------------------------------------------------------------------
_stats = {"rewrites": {}, "fallbacks": {}, "traces": 0}


def reset_stats():
    _stats["rewrites"] = {}
    _stats["fallbacks"] = {}
    _stats["traces"] = 0


def summary():
    """Per-process pass stats for bench/capture records: pattern →
    rewrite count, ``pattern:reason`` → fallback count, traces seen."""
    return {"rewrites": dict(_stats["rewrites"]),
            "fallbacks": dict(_stats["fallbacks"]),
            "traces": _stats["traces"]}


def _note_rewrite(pattern):
    _stats["rewrites"][pattern] = _stats["rewrites"].get(pattern, 0) + 1
    try:
        from ..observability.telemetry import get_telemetry
        get_telemetry().fusion_rewrite(pattern)
    except Exception:
        pass


def _note_fallback(pattern, reason):
    key = f"{pattern}:{reason}"
    _stats["fallbacks"][key] = _stats["fallbacks"].get(key, 0) + 1
    try:
        from ..observability.telemetry import get_telemetry
        get_telemetry().fusion_fallback(pattern, reason)
    except Exception:
        pass


def _backend():
    """``("pallas", None)`` where ``device.pallas_dispatch`` selects the
    kernels, else ``("xla", reason)``."""
    if not _device.on_tpu():
        return "xla", "not_tpu"
    if not _device.mosaic_can_lower():
        return "xla", "gspmd_mesh"
    return "pallas", None


# ---------------------------------------------------------------------------
# jaxpr graph view + matching helpers
# ---------------------------------------------------------------------------
_OUT = -1          # consumer sentinel for jaxpr outvars


def _is_lit(v):
    return isinstance(v, jcore.Literal)


def _scalar_lit(v):
    """Python float of a rank-0 Literal, else None."""
    if not _is_lit(v):
        return None
    try:
        import numpy as np
        if np.ndim(v.val) != 0:
            return None
        return float(v.val)
    except Exception:
        return None


def _split_lit(eqn):
    """(var, scalar) for a binary eqn with exactly one scalar-literal
    operand, else (None, None)."""
    a, b = eqn.invars
    la, lb = _scalar_lit(a), _scalar_lit(b)
    if la is None and lb is not None:
        return a, lb
    if lb is None and la is not None:
        return b, la
    return None, None


def _coef_close(val, ref):
    """Coefficient-literal compare tolerant of reduced-precision
    literals: a bf16 graph stores sqrt(2/pi) as 0.796875."""
    return val is not None and abs(val - ref) <= 0.01 * abs(ref)


def _conv_src(g, v):
    """Follow one ``convert_element_type`` producer of ``v``: (source
    var, convert eqn idx), or ``(v, None)`` when ``v`` is not a cast.
    AMP graphs re-emit a separate cast per ``.astype`` call site, so
    identity checks go through this to reach the shared source."""
    ci = g.pe(v, "convert_element_type")
    if ci is None:
        return v, None
    s = g.eqns[ci].invars[0]
    if _is_lit(s):
        return v, None
    return s, ci


class _Graph:
    def __init__(self, jaxpr):
        self.eqns = list(jaxpr.eqns)
        self.producer_idx = {}
        self.consumers = {}
        for i, e in enumerate(self.eqns):
            for v in e.outvars:
                self.producer_idx[v] = i
            for v in e.invars:
                if not _is_lit(v):
                    self.consumers.setdefault(v, []).append(i)
        for v in jaxpr.outvars:
            if not _is_lit(v):
                self.consumers.setdefault(v, []).append(_OUT)

    def producer(self, v):
        if _is_lit(v):
            return None
        return self.producer_idx.get(v)

    def pe(self, v, prim):
        """Producing eqn of ``v`` if its primitive is ``prim``."""
        i = self.producer(v)
        if i is None or self.eqns[i].primitive.name != prim:
            return None
        return i

    def sole_consumer(self, v, prim=None):
        cons = self.consumers.get(v, [])
        if len(cons) != 1 or cons[0] == _OUT:
            return None
        if prim is not None and \
                self.eqns[cons[0]].primitive.name != prim:
            return None
        return cons[0]


class Cluster:
    """One matched, rewritable subgraph."""
    __slots__ = ("pattern", "covered", "root", "invars", "outvar", "meta")

    def __init__(self, pattern, covered, invars, outvar, meta):
        self.pattern = pattern
        self.covered = frozenset(covered)
        self.root = max(covered)
        self.invars = list(invars)
        self.outvar = outvar
        self.meta = dict(meta)


def _closed(g, covered, outvar):
    """True when no interior value of the cluster escapes: every outvar
    of a covered eqn (except the cluster output) is consumed only by
    covered eqns — the structural eligibility test."""
    for i in covered:
        if g.eqns[i].effects:
            return False
        for ov in g.eqns[i].outvars:
            if ov is outvar:
                continue
            for ci in g.consumers.get(ov, []):
                if ci == _OUT or ci not in covered:
                    return False
    return True


def _absorb_bias_vec(g, eqn, val_var):
    """For ``add(val, broadcast_in_dim(b))`` (either order) with 1-D
    ``b`` whose broadcast is solely consumed here: (b_var, bcast_idx),
    else (None, None)."""
    for a, other in ((eqn.invars[0], eqn.invars[1]),
                     (eqn.invars[1], eqn.invars[0])):
        if a is not val_var or _is_lit(other):
            continue
        bi = g.pe(other, "broadcast_in_dim")
        if bi is None:
            continue
        src = g.eqns[bi].invars[0]
        if _is_lit(src) or src.aval.ndim != 1:
            continue
        if g.sole_consumer(g.eqns[bi].outvars[0]) is None:
            continue
        return src, bi
    return None, None


def _simple_dot(eqn, lhs_ndim):
    """True for an unbatched last-dim × dim-0 matmul with 2-D rhs."""
    if eqn.primitive.name != "dot_general":
        return False
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    return (tuple(lc), tuple(rc)) == ((lhs_ndim - 1,), (0,)) and \
        not lb and not rb and eqn.invars[1].aval.ndim == 2


# ---------------------------------------------------------------------------
# matcher: layer_norm / residual_ln / ln_matmul
# ---------------------------------------------------------------------------
def _match_ln(g, ri, claimed):
    eqns = g.eqns
    if eqns[ri].primitive.name != "rsqrt":
        return None
    ai = g.producer(eqns[ri].invars[0])
    if ai is None or eqns[ai].primitive.name != "add":
        return None
    var_v, eps = _split_lit(eqns[ai])
    if var_v is None:
        return None
    vi = g.pe(var_v, "jit")
    if vi is None or eqns[vi].params.get("name") != "_var":
        return None
    # jnp.var(x, ddof): second operand must be the ddof literal 0
    ddof = _scalar_lit(eqns[vi].invars[1]) \
        if len(eqns[vi].invars) > 1 else 0.0
    if ddof != 0.0:
        return None
    x = eqns[vi].invars[0]
    if _is_lit(x) or x.aval.ndim < 2:
        return None
    d = x.aval.shape[-1]
    # AMP models widen the soup with a cast per .astype call site; the
    # three stats reads then see three distinct convert outputs of one
    # shared source — identity checks go through the source var
    x_src, cva = _conv_src(g, x)
    stats_dtype = x.aval.dtype

    def same_as_x(v):
        """v is the stats input, or another cast of its source to the
        same stats dtype: (True, convert idx|None)."""
        if v is x:
            return True, None
        s, ci = _conv_src(g, v)
        if ci is not None and s is x_src and \
                v.aval.dtype == stats_dtype:
            return True, ci
        return False, None

    # (x - mean) * rstd, with mean = div(bcast(reduce_sum(x)), d)
    mi = g.sole_consumer(eqns[ri].outvars[0], "mul")
    if mi is None:
        return None
    sub_v = eqns[mi].invars[0] if eqns[mi].invars[1] is \
        eqns[ri].outvars[0] else eqns[mi].invars[1]
    si = g.pe(sub_v, "sub")
    if si is None:
        return None
    ok, c_sub = same_as_x(eqns[si].invars[0])
    if not ok:
        return None
    mean_v = eqns[si].invars[1]
    di = g.pe(mean_v, "div")
    if di is None or _scalar_lit(eqns[di].invars[1]) != float(d):
        return None
    bi = g.pe(eqns[di].invars[0], "broadcast_in_dim")
    if bi is None:
        return None
    rsi = g.pe(eqns[bi].invars[0], "reduce_sum")
    if rsi is None or \
            tuple(eqns[rsi].params["axes"]) != (x.aval.ndim - 1,):
        return None
    ok, c_mean = same_as_x(eqns[rsi].invars[0])
    if not ok:
        return None

    covered = {rsi, bi, di, vi, si, ai, ri, mi}
    for ci in (cva, c_sub, c_mean):
        if ci is not None:
            covered.add(ci)
    y = eqns[mi].outvars[0]
    w_var = b_var = None

    # optional cast between normalization and affine (AMP: stats run in
    # f32, the affine in the model dtype)
    ci0 = g.sole_consumer(y, "convert_element_type")
    if ci0 is not None:
        covered.add(ci0)
        y = eqns[ci0].outvars[0]
    affine_dtype = y.aval.dtype

    # optional affine: * broadcast(w) then + broadcast(b)
    wi = g.sole_consumer(y, "mul")
    if wi is not None:
        wv, wbi = _absorb_bias_vec(g, eqns[wi], y)
        if wv is not None and wv.aval.shape == (d,):
            w_var = wv
            covered |= {wi, wbi}
            y = eqns[wi].outvars[0]
    bi2 = g.sole_consumer(y, "add")
    if bi2 is not None:
        bv, bbi = _absorb_bias_vec(g, eqns[bi2], y)
        if bv is not None and bv.aval.shape == (d,):
            b_var = bv
            covered |= {bi2, bbi}
            y = eqns[bi2].outvars[0]

    # optional trailing convert (bf16 models cast the f32 soup back)
    ci = g.sole_consumer(y, "convert_element_type")
    if ci is not None:
        covered.add(ci)
        y = eqns[ci].outvars[0]
    ln_dtype = y.aval.dtype

    # optional residual: absorb the producing add when the sum is
    # consumed only inside the cluster (post-LN blocks; a pre-LN
    # residual also feeds the next block's add and stays outside)
    res_in = None
    pi = g.producer(x_src)
    if pi is not None and eqns[pi].primitive.name == "add" and \
            not any(_is_lit(v) for v in eqns[pi].invars) and \
            eqns[pi].invars[0].aval.shape == x_src.aval.shape and \
            eqns[pi].invars[1].aval.shape == x_src.aval.shape and \
            set(g.consumers.get(x_src, [])) <= covered:
        covered.add(pi)
        res_in = (eqns[pi].invars[0], eqns[pi].invars[1])

    # optional matmul epilogue: LN output as the lhs of one plain matmul
    mw_var = mb_var = None
    pref = None
    dmi = g.sole_consumer(y, "dot_general")
    if dmi is not None and dmi not in claimed and \
            _simple_dot(eqns[dmi], y.aval.ndim) and \
            eqns[dmi].invars[0] is y and \
            not _is_lit(eqns[dmi].invars[1]):
        mw_var = eqns[dmi].invars[1]
        pref = eqns[dmi].params.get("preferred_element_type")
        covered.add(dmi)
        y = eqns[dmi].outvars[0]
        abi = g.sole_consumer(y, "add")
        if abi is not None and abi not in claimed:
            bv, bbi = _absorb_bias_vec(g, eqns[abi], y)
            if bv is not None:
                mb_var = bv
                covered |= {abi, bbi}
                y = eqns[abi].outvars[0]

    if mw_var is not None:
        pattern = "ln_matmul"
    elif res_in is not None:
        pattern = "residual_ln"
    else:
        pattern = "layer_norm"

    invars = list(res_in) if res_in is not None else [x_src]
    meta = {"eps": float(eps), "res": res_in is not None,
            "w": w_var is not None, "b": b_var is not None,
            "matmul": mw_var is not None, "mbias": mb_var is not None,
            "pref": pref, "ln_dtype": ln_dtype,
            "stats_dtype": stats_dtype, "affine_dtype": affine_dtype,
            "out_dtype": y.aval.dtype}
    for v in (w_var, b_var, mw_var, mb_var):
        if v is not None:
            invars.append(v)
    return Cluster(pattern, covered, invars, y, meta)


# ---------------------------------------------------------------------------
# matcher: matmul + bias + gelu (tanh and erf lowerings)
# ---------------------------------------------------------------------------
def _match_mbg_pre(g, z):
    """Locate the matmul (+ bias) producing the gelu argument ``z``:
    (covered, x, w, b, pref) or None."""
    eqns = g.eqns
    b_var = None
    covered = set()
    di = g.producer(z)
    if di is None:
        return None
    if eqns[di].primitive.name == "add":
        a, b = eqns[di].invars
        dot_v = a if g.pe(a, "dot_general") is not None else b
        bv, bbi = _absorb_bias_vec(g, eqns[di], dot_v)
        if bv is None:
            return None
        b_var = bv
        covered |= {di, bbi}
        di = g.pe(dot_v, "dot_general")
        if di is None:
            return None
    if eqns[di].primitive.name != "dot_general":
        return None
    x = eqns[di].invars[0]
    if _is_lit(x) or not _simple_dot(eqns[di], x.aval.ndim):
        return None
    covered.add(di)
    return covered, x, eqns[di].invars[1], b_var, \
        eqns[di].params.get("preferred_element_type")


def _match_mbg_tanh(g, ti):
    eqns = g.eqns
    if eqns[ti].primitive.name != "tanh":
        return None
    ji = g.producer(eqns[ti].invars[0])
    if ji is None or eqns[ji].primitive.name != "mul":
        return None
    inner_v, coef = _split_lit(eqns[ji])
    if inner_v is None or not _coef_close(coef, _TANH_COEF):
        return None
    ii = g.pe(inner_v, "add")
    if ii is None:
        return None
    # add(z, mul(0.044715, z**3)) — z on either side
    z = cub = None
    for a, b in ((eqns[ii].invars[0], eqns[ii].invars[1]),
                 (eqns[ii].invars[1], eqns[ii].invars[0])):
        hi = g.pe(b, "mul")
        if hi is None:
            continue
        gv, c3 = _split_lit(eqns[hi])
        if gv is None or not _coef_close(c3, _TANH_CUBIC):
            continue
        pi = g.pe(gv, "integer_pow")
        if pi is None or eqns[pi].params.get("y") != 3 or \
                eqns[pi].invars[0] is not a:
            continue
        z, cub = a, (hi, pi)
        break
    if z is None:
        return None
    li = g.sole_consumer(eqns[ti].outvars[0], "add")
    if li is None:
        return None
    lv, one = _split_lit(eqns[li])
    if lv is None or one != 1.0:
        return None
    mi = g.sole_consumer(eqns[li].outvars[0], "mul")
    if mi is None:
        return None
    mv, half = _split_lit(eqns[mi])
    if mv is None or half != 0.5:
        return None
    ni = g.sole_consumer(eqns[mi].outvars[0], "mul")
    if ni is None or z not in eqns[ni].invars:
        return None
    pre = _match_mbg_pre(g, z)
    if pre is None:
        return None
    covered, x, w, b, pref = pre
    covered |= {ji, ii, cub[0], cub[1], ti, li, mi, ni}
    y = eqns[ni].outvars[0]
    invars = [x, w] + ([b] if b is not None else [])
    return Cluster("matmul_bias_gelu", covered, invars, y,
                   {"approximate": True, "bias": b is not None,
                    "pref": pref, "out_dtype": y.aval.dtype})


def _match_mbg_erf(g, ei):
    eqns = g.eqns
    if eqns[ei].primitive.name != "erfc":
        return None
    mi = g.producer(eqns[ei].invars[0])
    if mi is None or eqns[mi].primitive.name != "mul":
        return None
    neg_v, coef = _split_lit(eqns[mi])
    if neg_v is None or not _coef_close(coef, _SQRT_HALF):
        return None
    ci = g.pe(neg_v, "neg")
    if ci is None:
        return None
    z = eqns[ci].invars[0]
    fi = g.sole_consumer(eqns[ei].outvars[0], "mul")
    if fi is None:
        return None
    half_v = eqns[fi].invars[0] if eqns[fi].invars[1] is \
        eqns[ei].outvars[0] else eqns[fi].invars[1]
    hi = g.pe(half_v, "mul")
    if hi is None:
        return None
    zv, half = _split_lit(eqns[hi])
    if zv is not z or half != 0.5:
        return None
    covered = {mi, ci, ei, fi, hi}
    y = eqns[fi].outvars[0]
    cpi = g.sole_consumer(y, "copy")
    if cpi is not None:
        covered.add(cpi)
        y = eqns[cpi].outvars[0]
    pre = _match_mbg_pre(g, z)
    if pre is None:
        return None
    pcov, x, w, b, pref = pre
    covered |= pcov
    invars = [x, w] + ([b] if b is not None else [])
    return Cluster("matmul_bias_gelu", covered, invars, y,
                   {"approximate": False, "bias": b is not None,
                    "pref": pref, "out_dtype": y.aval.dtype})


# ---------------------------------------------------------------------------
# matcher: attention block (qk matmul + scale + softmax + pv matmul)
# ---------------------------------------------------------------------------
_QK_DIMS = (((3,), (3,)), ((0, 1), (0, 1)))
_PV_DIMS = (((3,), (2,)), ((0, 1), (0, 1)))


def _dot_dims(eqn):
    ((lc, rc), (lb, rb)) = eqn.params["dimension_numbers"]
    return ((tuple(lc), tuple(rc)), (tuple(lb), tuple(rb)))


def _match_attention(g, pi):
    eqns = g.eqns
    if eqns[pi].primitive.name != "dot_general" or \
            eqns[pi].invars[0].aval.ndim != 4 or \
            _dot_dims(eqns[pi]) != _PV_DIMS:
        return None
    p, v = eqns[pi].invars
    if _is_lit(p) or _is_lit(v):
        return None
    p_dtype = p.aval.dtype
    pv_pref = eqns[pi].params.get("preferred_element_type")
    # AMP casts the f32 softmax island back to the model dtype before
    # the pv matmul — step through the cast
    p_src, c_p = _conv_src(g, p)
    # softmax chain: div(exp, bcast(reduce_sum(exp)))
    dvi = g.pe(p_src, "div")
    if dvi is None:
        return None
    exp_v, den_v = eqns[dvi].invars
    xpi = g.pe(exp_v, "exp")
    bgi = g.pe(den_v, "broadcast_in_dim")
    if xpi is None or bgi is None:
        return None
    rsi = g.pe(eqns[bgi].invars[0], "reduce_sum")
    if rsi is None or eqns[rsi].invars[0] is not exp_v:
        return None
    sbi = g.pe(eqns[xpi].invars[0], "sub")
    if sbi is None:
        return None
    scores, max_b = eqns[sbi].invars
    sgi = g.pe(max_b, "stop_gradient")
    if sgi is None:
        return None
    bbi = g.pe(eqns[sgi].invars[0], "broadcast_in_dim")
    if bbi is None:
        return None
    mxi = g.pe(eqns[bbi].invars[0], "max")
    if mxi is None:
        return None
    rm_v, _ninf = _split_lit(eqns[mxi])
    rmi = g.pe(rm_v, "reduce_max") if rm_v is not None else None
    if rmi is None or eqns[rmi].invars[0] is not scores:
        return None
    covered = {pi, dvi, xpi, bgi, rsi, sbi, sgi, bbi, mxi, rmi}
    if c_p is not None:
        covered.add(c_p)
    s_dtype = eqns[xpi].outvars[0].aval.dtype

    # causal mask: scores = _where(tril(...), scaled, -inf)
    causal = False
    wi = g.producer(scores)
    if wi is not None and eqns[wi].primitive.name == "jit" and \
            eqns[wi].params.get("name") == "_where":
        tri = g.pe(eqns[wi].invars[0], "jit")
        if tri is None or eqns[tri].params.get("name") != "tril":
            return None
        covered |= {wi, tri}
        ti = g.pe(eqns[tri].invars[0], "broadcast_in_dim")
        if ti is not None:
            covered.add(ti)
        causal = True
        scores = eqns[wi].invars[1]

    # scale: mul(qk, sm_scale) — optional (sm_scale == 1 emits no mul);
    # AMP interposes a cast between the bf16 qk matmul and the f32 scale
    sm_scale = 1.0
    sci = g.producer(scores)
    if sci is not None and eqns[sci].primitive.name == "mul":
        qk_v, sc = _split_lit(eqns[sci])
        if qk_v is not None and \
                g.pe(_conv_src(g, qk_v)[0], "dot_general") is not None:
            sm_scale = float(sc)
            covered.add(sci)
            scores = qk_v
    scores, c_qk = _conv_src(g, scores)
    if c_qk is not None:
        covered.add(c_qk)
    sci = g.producer(scores)
    if sci is None or eqns[sci].primitive.name != "dot_general" or \
            _dot_dims(eqns[sci]) != _QK_DIMS:
        return None
    q, k = eqns[sci].invars
    if _is_lit(q) or _is_lit(k):
        return None
    covered.add(sci)
    y = eqns[pi].outvars[0]
    return Cluster("attention_block", covered, [q, k, v], y,
                   {"causal": causal, "sm_scale": sm_scale,
                    "qk_pref": eqns[sci].params.get(
                        "preferred_element_type"),
                    "pv_pref": pv_pref, "s_dtype": s_dtype,
                    "p_dtype": p_dtype, "out_dtype": y.aval.dtype})


# ---------------------------------------------------------------------------
# pass driver
# ---------------------------------------------------------------------------
def match_jaxpr(jaxpr, disabled=None):
    """Match all rewritable clusters in ``jaxpr``, highest-priority
    pattern first (attention → gelu → LN family, so e.g. an MLP fc1 dot
    is claimed by the gelu cluster and the preceding LN falls back to a
    bare layer_norm).  Returns non-overlapping, closure-checked
    :class:`Cluster` objects in program order."""
    if disabled is None:
        disabled = disabled_patterns()
    g = _Graph(jaxpr)
    clusters, claimed = [], set()

    def take(cl):
        if cl is None or cl.pattern in disabled:
            return
        if cl.covered & claimed:
            return
        if not _closed(g, cl.covered, cl.outvar):
            return
        claimed.update(cl.covered)
        clusters.append(cl)

    for i in range(len(g.eqns)):
        take(_match_attention(g, i))
    for i in range(len(g.eqns)):
        take(_match_mbg_tanh(g, i))
        take(_match_mbg_erf(g, i))
    for i in range(len(g.eqns)):
        take(_match_ln(g, i, claimed))
    clusters.sort(key=lambda c: c.root)
    return clusters


def count_patterns(fn, *args, **kwargs):
    """Pattern → match count for ``fn(*args)`` without executing it —
    the bench/tests introspection entry."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    counts = {}
    for cl in match_jaxpr(closed.jaxpr):
        counts[cl.pattern] = counts.get(cl.pattern, 0) + 1
    return counts


def match_report(jaxpr, disabled=None):
    """Eligibility census for the graph auditor (``tools/audit``):
    like :func:`match_jaxpr`, but additionally keeps the structural
    matches that FAILED the closure test, each with a string naming the
    first blocking escape.

    Returns ``(clusters, near_misses)``: the eligible clusters exactly
    as :func:`match_jaxpr` would pick them, plus a list of
    ``(cluster, blocker)`` pairs where ``blocker`` names the interior
    value and the outside consumer that pins it (the jaxpr output, a
    foreign eqn, or an effectful member eqn)."""
    if disabled is None:
        disabled = disabled_patterns()
    g = _Graph(jaxpr)
    clusters, near, claimed, near_claimed = [], [], set(), set()

    def _blocker(cl):
        for i in sorted(cl.covered):
            eqn = g.eqns[i]
            if eqn.effects:
                return f"member eqn {eqn.primitive.name} carries effects"
            for ov in eqn.outvars:
                if ov is cl.outvar:
                    continue
                for ci in g.consumers.get(ov, []):
                    if ci == _OUT:
                        return (f"interior {eqn.primitive.name} result "
                                f"{ov.aval.str_short()} escapes to the "
                                "program output")
                    if ci not in cl.covered:
                        return (f"interior {eqn.primitive.name} result "
                                f"{ov.aval.str_short()} escapes to eqn "
                                f"{g.eqns[ci].primitive.name}")
        return None

    def take(cl):
        if cl is None or cl.pattern in disabled:
            return
        if cl.covered & claimed:
            return
        b = _blocker(cl)
        if b is not None:
            if not (cl.covered & near_claimed):
                near_claimed.update(cl.covered)
                near.append((cl, b))
            return
        claimed.update(cl.covered)
        clusters.append(cl)

    for i in range(len(g.eqns)):
        take(_match_attention(g, i))
    for i in range(len(g.eqns)):
        take(_match_mbg_tanh(g, i))
        take(_match_mbg_erf(g, i))
    for i in range(len(g.eqns)):
        take(_match_ln(g, i, claimed))
    clusters.sort(key=lambda c: c.root)
    near.sort(key=lambda nb: nb[0].root)
    return clusters, near


def _bvec(v, ndim):
    return jnp.reshape(v, (1,) * (ndim - 1) + (v.shape[-1],))


def _cluster_fn(cl):
    """Build the callable replacing cluster ``cl``: Pallas block kernel
    on TPU, inline XLA mirror of the matched soup otherwise."""
    pattern, meta = cl.pattern, cl.meta
    backend, reason = _backend()
    if backend != "pallas":
        _note_fallback(pattern, reason)
    from . import fused_kernels as fk

    if pattern == "attention_block":
        causal, scale = meta["causal"], meta["sm_scale"]

        def call(q, k, v):
            if backend == "pallas":
                out = fk.fused_attention_block(
                    q, k, v, causal=causal, sm_scale=scale,
                    interpret=False)
            else:
                s = jax.lax.dot_general(
                    q, k, dimension_numbers=_QK_DIMS,
                    preferred_element_type=meta.get("qk_pref"))
                s = s.astype(meta.get("s_dtype", s.dtype)) * scale
                if causal:
                    mask = jnp.tril(jnp.ones(
                        (q.shape[2], k.shape[2]), bool))
                    s = jnp.where(mask, s, -jnp.inf)
                p = jax.nn.softmax(s, axis=-1)
                p = p.astype(meta.get("p_dtype", p.dtype))
                out = jax.lax.dot_general(
                    p, v, dimension_numbers=_PV_DIMS,
                    preferred_element_type=meta.get("pv_pref"))
            return out.astype(meta["out_dtype"])
        return call

    if pattern == "matmul_bias_gelu":
        approx, pref = meta["approximate"], meta["pref"]

        def call(x, w, b=None):
            if backend == "pallas":
                rows = 1
                for s in x.shape[:-1]:
                    rows *= s
                y = fk.fused_matmul_bias_gelu(
                    x.reshape(rows, x.shape[-1]), w, b,
                    approximate=approx, interpret=False)
                out = y.reshape(x.shape[:-1] + (w.shape[1],))
            else:
                z = jax.lax.dot_general(
                    x, w,
                    dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
                    preferred_element_type=pref)
                if b is not None:
                    z = z + _bvec(b, z.ndim)
                out = jax.nn.gelu(z, approximate=approx)
            return out.astype(meta["out_dtype"])
        return call

    # LN family
    eps = meta["eps"]

    def call(*vals):
        it = iter(vals)
        if meta["res"]:
            x, res = next(it), next(it)
        else:
            x, res = next(it), None
        w = next(it) if meta["w"] else None
        b = next(it) if meta["b"] else None
        mw = next(it) if meta["matmul"] else None
        mb = next(it) if meta["mbias"] else None
        if backend == "pallas":
            d = x.shape[-1]
            rows = 1
            for s in x.shape[:-1]:
                rows *= s
            x2 = x.reshape(rows, d)
            r2 = res.reshape(rows, d) if res is not None else None
            if meta["matmul"]:
                y = fk.fused_ln_matmul(x2, mw, w, b, mb, r2,
                                       epsilon=eps, interpret=False)
                out = y.reshape(x.shape[:-1] + (mw.shape[1],))
            else:
                y = fk.fused_layer_norm(x2, w, b, r2, epsilon=eps,
                                        interpret=False)
                out = y.reshape(x.shape)
            return out.astype(meta["out_dtype"])
        # XLA mirror of the matched soup
        if res is not None:
            x = x + res
        xf = x.astype(meta.get("stats_dtype", jnp.float32))
        m = jnp.mean(xf, axis=-1, keepdims=True)
        va = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - m) * jax.lax.rsqrt(va + eps)
        # AMP casts back to the model dtype BEFORE the affine — mirror it
        y = y.astype(meta.get("affine_dtype", y.dtype))
        if w is not None:
            y = y * _bvec(w, y.ndim)
        if b is not None:
            y = y + _bvec(b, y.ndim)
        if meta["matmul"]:
            y = y.astype(meta["ln_dtype"])
            y = jax.lax.dot_general(
                y, mw, dimension_numbers=(((y.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=meta["pref"])
            if mb is not None:
                y = y + _bvec(mb, y.ndim)
        return y.astype(meta["out_dtype"])
    return call


def _eval_rewritten(jaxpr, consts, args, plan):
    """Evaluate ``jaxpr`` like ``core.eval_jaxpr`` but with each
    cluster's covered eqns skipped and its fused call bound at the
    cluster root."""
    env = {}

    def read(v):
        return v.val if _is_lit(v) else env[v]

    def write(v, val):
        env[v] = val

    for v, c in zip(jaxpr.constvars, consts):
        write(v, c)
    for v, a in zip(jaxpr.invars, args):
        write(v, a)

    outer_stack = _source_info.current_name_stack()
    by_idx = {}
    for cl in plan:
        fn = _cluster_fn(cl)
        for i in cl.covered:
            by_idx[i] = (cl, fn)

    for idx, eqn in enumerate(jaxpr.eqns):
        hit = by_idx.get(idx)
        if hit is not None and idx != hit[0].root:
            continue
        # re-binding drops the equation's source information: re-enter
        # its name stack (jax.named_scope), as core.eval_jaxpr does, so
        # a rewritten step keeps its scopes in HLO and profiler traces
        # (a fused cluster takes its root equation's)
        with _source_info.set_name_stack(
                outer_stack + eqn.source_info.name_stack):
            if hit is not None:
                cl, fn = hit
                write(cl.outvar, fn(*[read(v) for v in cl.invars]))
                continue
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(
                *subfuns, *[read(v) for v in eqn.invars], **bind_params)
        if eqn.primitive.multiple_results:
            for v, a in zip(eqn.outvars, ans):
                write(v, a)
        else:
            write(eqn.outvars[0], ans)
    return [read(v) for v in jaxpr.outvars]


def wrap(fn):
    """Apply the fusion pass to ``fn`` at trace time: re-trace it to a
    jaxpr, rewrite matched clusters to block-fused kernel calls, and
    evaluate the rewritten graph (in the caller's trace, so this
    composes with jit/grad/capture).  Declines (calls ``fn`` untouched)
    when the pass is disabled or nothing matches; an exception inside
    the pass propagates."""
    import functools

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if not fusion_enabled():
            return fn(*args, **kwargs)
        flat, in_tree = jax.tree_util.tree_flatten((args, kwargs))

        def flat_fn(*leaves):
            a, kw = jax.tree_util.tree_unflatten(in_tree, leaves)
            return fn(*a, **kw)

        closed, out_shape = jax.make_jaxpr(
            flat_fn, return_shape=True)(*flat)
        plan = match_jaxpr(closed.jaxpr)
        _stats["traces"] += 1
        if not plan:
            return fn(*args, **kwargs)
        for cl in plan:
            _note_rewrite(cl.pattern)
        out_flat = _eval_rewritten(closed.jaxpr, closed.consts, flat,
                                   plan)
        _, out_tree = jax.tree_util.tree_flatten(out_shape)
        return jax.tree_util.tree_unflatten(out_tree, out_flat)

    wrapped.__wrapped__ = fn
    return wrapped
