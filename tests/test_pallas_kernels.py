"""ops/pallas kernels under the interpreter (JAX_PLATFORMS=cpu):
fwd/bwd equivalence against the XLA reference paths, the adagrad
update, and the selection/fallback machinery.

The contract these tests pin (ops/pallas/__init__.py): the XLA forms
in ops/twotower.py remain the numerical reference; a kernel may only
replace one if it agrees to <=1e-5 in f32 — including in-batch
duplicate users/items, zero-weight padding rows, and a RAGGED last
grid tile — and selection must fall back (never fail) everywhere a
kernel is ineligible.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas as plk
from predictionio_tpu.ops.pallas.embed_update import pallas_rowwise_adagrad
from predictionio_tpu.ops.pallas import flash_ce as flash_ce_mod
from predictionio_tpu.ops.pallas.flash_ce import (
    backward_form,
    make_flash_ce,
    pallas_blockwise_ce,
)
from predictionio_tpu.ops.twotower import (
    TwoTowerConfig,
    TwoTowerTrainer,
    _dense_softmax_ce,
    _make_blockwise_ce_vjp,
    _rowwise_adagrad,
)


def _batch(B, D, seed=9, n_users=60, n_items=40, n_pad=17,
           uniform_w=True):
    """Unit-norm towers + index vectors with many in-batch duplicates
    and a zero-weight padded tail — the full masking surface.
    ``uniform_w=False`` draws real-valued weights (the
    ``weight_by_rating`` path), exercising the w-asymmetric terms of
    the loss and backward that 0/1 weights cannot distinguish."""
    rng = np.random.default_rng(seed)
    u = rng.normal(size=(B, D)).astype(np.float32)
    v = rng.normal(size=(B, D)).astype(np.float32)
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    v /= np.linalg.norm(v, axis=1, keepdims=True)
    u_idx = rng.integers(0, n_users, B).astype(np.int32)
    i_idx = rng.integers(0, n_items, B).astype(np.int32)
    w = (np.ones(B, np.float32) if uniform_w
         else (0.5 + 4.0 * rng.random(B)).astype(np.float32))
    if n_pad:
        w[-n_pad:] = 0.0
    return (jnp.asarray(u), jnp.asarray(v), jnp.asarray(u_idx),
            jnp.asarray(i_idx), jnp.asarray(w))


@pytest.mark.parametrize("cdt_name,uniform_w,l_rtol,g_rtol,g_atol", [
    ("float32", True, 1e-5, 1e-4, 1e-6),
    # weight_by_rating shape: real-valued weights exercise the
    # w-asymmetric loss/backward terms 0/1 weights cannot distinguish
    ("float32", False, 1e-5, 1e-4, 1e-6),
    # bf16 tile logits: same tolerance story as the XLA blockwise test
    # (quantization under different summation orders)
    ("bfloat16", True, 5e-3, 1e-1, 2e-3),
])
def test_flash_ce_matches_xla_paths(cdt_name, uniform_w, l_rtol, g_rtol,
                                    g_atol):
    """Loss AND grads of the Pallas flash-CE agree with the dense
    reference and the XLA blockwise VJP it replaces."""
    B, D, block = 256, 16, 64
    u, v, u_idx, i_idx, w = _batch(B, D, uniform_w=uniform_w)
    cdt = jnp.dtype(cdt_name)

    def dense(u_, v_):
        return _dense_softmax_ce(u_, v_, u_idx, i_idx, w, 0.07, cdt)

    xla = _make_blockwise_ce_vjp(u_idx, i_idx, w, 0.07, block, cdt, B)
    flash = make_flash_ce(u_idx, i_idx, w, 0.07, cdt, B,
                          interpret=True, block=block)

    ld, (gdu, gdv) = jax.value_and_grad(dense, argnums=(0, 1))(u, v)
    lx, (gxu, gxv) = jax.value_and_grad(xla, argnums=(0, 1))(u, v)
    lf, (gfu, gfv) = jax.value_and_grad(flash, argnums=(0, 1))(u, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=l_rtol)
    np.testing.assert_allclose(float(lf), float(lx), rtol=l_rtol)
    for got, ref in ((gfu, gdu), (gfv, gdv), (gfu, gxu), (gfv, gxv)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=g_rtol, atol=g_atol)


@pytest.mark.parametrize("B", [200, 130])
def test_flash_ce_ragged_last_tile(B):
    """B not divisible by the tile: the zero-pad path must stay exact
    vs the dense reference (which needs no padding)."""
    D, block = 16, 64
    u, v, u_idx, i_idx, w = _batch(B, D, seed=4, n_pad=9)

    def dense(u_, v_):
        return _dense_softmax_ce(u_, v_, u_idx, i_idx, w, 0.07,
                                 jnp.float32)

    flash = make_flash_ce(u_idx, i_idx, w, 0.07, jnp.float32, B,
                          interpret=True, block=block)
    ld, (gdu, gdv) = jax.value_and_grad(dense, argnums=(0, 1))(u, v)
    lf, (gfu, gfv) = jax.value_and_grad(flash, argnums=(0, 1))(u, v)
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gfu), np.asarray(gdu),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gfv), np.asarray(gdv),
                               rtol=1e-4, atol=1e-6)
    assert gfu.shape == (B, D) and gfv.shape == (B, D)


def _tilewise_grads(u, v, u_idx, i_idx, w, temp, cdt, b):
    """Flash-CE's gradients as plain ``jax.numpy``, a Python loop over the
    tiles: the kernels' roundings (cdt logits, cdt divide, cdt coefficient,
    float32 accumulation) and their order (du[i] over j, dv[j] over i),
    none of their code."""
    f32 = jnp.float32
    B = u.shape[0]
    Bp = -(-B // b) * b
    S = Bp // b
    u, v, ui, ii, w = (jnp.pad(a, [(0, Bp - B)] + [(0, 0)] * (a.ndim - 1))
                       .reshape(S, b, *a.shape[1:])
                       for a in (u, v, u_idx, i_idx, w))
    rows = jnp.arange(Bp).reshape(S, b)

    def tile(i, j):
        L = jax.lax.dot_general(
            u[i].astype(cdt), v[j].astype(cdt), (((1,), (1,)), ((), ())),
            preferred_element_type=f32).astype(cdt)
        L = (L / temp).astype(f32)
        off = rows[i][:, None] != rows[j][None, :]
        ban_ui = ((ii[j][None, :] == ii[i][:, None])
                  | (w[j][None, :] <= 0.0)) & off
        ban_iu = ((ui[i][:, None] == ui[j][None, :])
                  | (w[i][:, None] <= 0.0)) & off
        return L, off, ban_ui, ban_iu

    # each tile's step under jit, as the interpreter runs a kernel's body:
    # XLA on the CPU may keep more than cdt between two fused operations,
    # and an eager loop would round where it does not
    @jax.jit
    def sums(i, j, sum_ui_i):
        L, _, ban_ui, ban_iu = tile(i, j)
        e = jnp.exp(L)
        return (sum_ui_i + jnp.sum(jnp.where(ban_ui, 0.0, e), axis=1),
                jnp.sum(jnp.where(ban_iu, 0.0, e), axis=0))

    @jax.jit
    def step(i, j, lse_ui_i, lse_iu_j, du_i, dv_j):
        L, off, ban_ui, ban_iu = tile(i, j)
        p_ui = jnp.where(ban_ui, 0.0, jnp.exp(L - lse_ui_i[:, None]))
        p_iu = jnp.where(ban_iu, 0.0, jnp.exp(L - lse_iu_j[None, :]))
        d = jnp.where(off, 0.0, 1.0)
        cc = ((w[i][:, None] * (p_ui - d) + w[j][None, :] * (p_iu - d))
              * scale).astype(cdt)
        du_i = du_i + jax.lax.dot_general(
            cc, v[j].astype(cdt), (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        dv_j = dv_j + jax.lax.dot_general(
            cc, u[i].astype(cdt), (((0,), (0,)), ((), ())),
            preferred_element_type=f32)
        real = (w[i][:, None] > 0) & (w[j][None, :] > 0)
        return du_i, dv_j, (ban_ui & real).any() & (ban_iu & real).any()

    sum_ui = [jnp.zeros((b,), f32) for _ in range(S)]
    iu = [[None] * S for _ in range(S)]
    for i in range(S):
        for j in range(S):
            sum_ui[i], iu[i][j] = sums(i, j, sum_ui[i])
    lse_ui = [jnp.log(s_) for s_ in sum_ui]
    lse_iu = [jnp.log(jnp.sum(jnp.stack([iu[i][j] for i in range(S)]),
                              axis=0)) for j in range(S)]
    scale = (1.0 / (2.0 * jnp.maximum(w.sum(), 1e-8) * temp)).astype(f32)
    du = [jnp.zeros(u.shape[1:], f32) for _ in range(S)]
    dv = [jnp.zeros(v.shape[1:], f32) for _ in range(S)]
    banned = {"diagonal": False, "off": False}
    for i in range(S):
        for j in range(S):
            du[i], dv[j], any_banned = step(i, j, lse_ui[i], lse_iu[j],
                                            du[i], dv[j])
            banned["diagonal" if i == j else "off"] |= bool(any_banned)
    return (np.concatenate(du)[:B], np.concatenate(dv)[:B], banned)


@pytest.mark.parametrize("B", [256, 200, 130])
@pytest.mark.parametrize("uniform_w", [True, False],
                         ids=["uniform", "ragged_w"])
@pytest.mark.parametrize("cdt_name", ["bfloat16", "float32"])
def test_flash_ce_one_pass_backward_is_the_tilewise_form(cdt_name, uniform_w,
                                                         B):
    """The one-pass backward's du and dv against the tile-by-tile form:
    same roundings, same accumulation order. Duplicate users and items
    ban pairs inside diagonal tiles and off them; 200 and 130 leave a
    ragged last tile."""
    D, block = 16, 64
    u, v, u_idx, i_idx, w = _batch(B, D, seed=B, uniform_w=uniform_w)
    cdt = jnp.dtype(cdt_name)
    assert backward_form(B, D, block) == "one_pass"
    flash = make_flash_ce(u_idx, i_idx, w, 0.07, cdt, B,
                          interpret=True, block=block)
    du, dv = jax.grad(flash, argnums=(0, 1))(u, v)
    ref_du, ref_dv, banned = _tilewise_grads(u, v, u_idx, i_idx, w, 0.07,
                                             cdt, block)
    assert banned == {"diagonal": True, "off": True}
    # the interpreter may reassociate a reduction: 1e-6 of the largest entry
    for got, ref in ((du, ref_du), (dv, ref_dv)):
        np.testing.assert_allclose(np.asarray(got), ref, rtol=0,
                                   atol=1e-6 * np.abs(ref).max())


def _pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub)
    return n


@pytest.mark.parametrize("B", [256, 200])
@pytest.mark.parametrize("cdt_name", ["bfloat16", "float32"])
def test_flash_ce_backward_form_follows_the_shape(monkeypatch, cdt_name, B):
    """Two kernels a step where the whole dv fits the VMEM budget, three
    past it; the choice moves neither gradient."""
    D, block = 16, 64
    u, v, u_idx, i_idx, w = _batch(B, D, seed=6, uniform_w=False)
    cdt = jnp.dtype(cdt_name)

    def grads():
        flash = make_flash_ce(u_idx, i_idx, w, 0.07, cdt, B,
                              interpret=True, block=block)
        vg = jax.value_and_grad(flash, argnums=(0, 1))
        return _pallas_calls(jax.make_jaxpr(vg)(u, v).jaxpr), vg(u, v)

    n_one, (l_one, g_one) = grads()
    # a chip too small to hold this dv beside a tile's working set
    monkeypatch.setattr(flash_ce_mod, "_vmem_bytes", lambda: 1 << 16)
    assert backward_form(B, D, block) == "two_pass"
    n_two, (l_two, g_two) = grads()
    assert (n_one, n_two) == (2, 3)
    assert float(l_one) == float(l_two)
    for one, two in zip(g_one, g_two):
        one, two = np.asarray(one), np.asarray(two)
        if cdt == jnp.float32:
            assert np.array_equal(one, two)
        else:
            # XLA on the CPU keeps more than bfloat16 where it fuses, and
            # the two bodies fuse apart: a few entries an ulp of float32
            # off under the interpreter (the chip's kernels round as
            # written: tools/flash_ce_probe.py compares them bit for bit)
            np.testing.assert_allclose(one, two, rtol=0,
                                       atol=1e-6 * np.abs(two).max())


@pytest.mark.parametrize("B,D,form", [
    (8192, 128, "one_pass"),     # the stretch cell: dv is 4 MiB
    (4096, 64, "one_pass"),
    (65536, 256, "one_pass"),    # 64 MiB resident of a v5e's 128
    (81920, 256, "one_pass"),    # 80 MiB: the largest the probe ran
    (98304, 256, "two_pass"),    # 96 MiB and the tiles: past three quarters
    (262144, 128, "two_pass"),
])
def test_flash_ce_backward_form_at_real_shapes(B, D, form):
    assert backward_form(B, D) == form


def test_flash_ce_one_call_form_jits():
    """The convenience wrapper traces under jit (how the epoch scan
    uses it) and returns a finite f32 scalar."""
    B, D = 128, 8
    u, v, u_idx, i_idx, w = _batch(B, D, seed=2, n_pad=5)

    @jax.jit
    def f(u_, v_):
        return pallas_blockwise_ce(u_, v_, u_idx, i_idx, w, 0.07,
                                   jnp.float32, interpret=True, block=32)

    out = f(u, v)
    assert out.dtype == jnp.float32 and bool(jnp.isfinite(out))


@pytest.mark.parametrize("N,E,B,vocab", [
    (64, 24, 37, 64),    # ragged tile + non-128 row width
    (128, 16, 32, 128),  # aligned
    (50, 8, 24, 6),      # duplicate-heavy: every tile collides
])
def test_pallas_adagrad_matches_xla(N, E, B, vocab):
    """The fused embedding-update equals _rowwise_adagrad — table AND
    accumulator — including duplicate indices within and across tiles
    (read-after-full-add scale semantics)."""
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.normal(size=(N, E)).astype(np.float32))
    acc = jnp.asarray(np.abs(rng.normal(size=N)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, vocab, B).astype(np.int32))
    grad = jnp.asarray(rng.normal(size=(B, E)).astype(np.float32))

    t_ref, a_ref = _rowwise_adagrad(table, acc, idx, grad, 0.03)
    t_k, a_k = pallas_rowwise_adagrad(table, acc, idx, grad, 0.03,
                                      interpret=True)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_ref),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref),
                               rtol=1e-5, atol=1e-6)


def test_pallas_adagrad_in_donated_jit():
    """The scan-body usage shape: jitted with donated buffers (the
    aliased in-place table update must compose with XLA donation)."""
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.normal(size=(40, 8)).astype(np.float32))
    acc = jnp.zeros((40,), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 40, 16).astype(np.int32))
    grad = jnp.asarray(rng.normal(size=(16, 8)).astype(np.float32))
    t_ref, a_ref = _rowwise_adagrad(table, acc, idx, grad, 0.05)

    f = jax.jit(lambda t, a: pallas_rowwise_adagrad(
        t, a, idx, grad, 0.05, interpret=True), donate_argnums=(0, 1))
    t_k, a_k = f(table, acc)
    np.testing.assert_allclose(np.asarray(t_k), np.asarray(t_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_ref),
                               rtol=1e-6, atol=1e-7)


# -- selection / fallback ----------------------------------------------------


def _positives(n=700, n_users=80, n_items=50, seed=1):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n_users, n), rng.integers(0, n_items, n),
            n_users, n_items)


def test_trainer_kernel_plan_defaults_off_on_cpu():
    """'auto' must NOT engage on a CPU backend (interpret mode is a
    test vehicle, not a production path) — existing CPU users keep the
    XLA forms untouched."""
    u, i, n_users, n_items = _positives()
    cfg = TwoTowerConfig(dim=8, epochs=1, batch_size=256, seed=3)
    tr = TwoTowerTrainer((u, i, None), n_users, n_items, cfg)
    assert tr.kernel_plan["flash_ce"] is False
    assert tr.kernel_plan["embed_update"] is False
    assert tr.kernel_plan["interpret"] is True  # cpu backend implies it


def test_trainer_kernel_plan_forced_on_engages_interpret():
    u, i, n_users, n_items = _positives()
    cfg = TwoTowerConfig(dim=8, epochs=1, batch_size=256, seed=3,
                         flash_ce_kernel="on", embed_update_kernel="on")
    tr = TwoTowerTrainer((u, i, None), n_users, n_items, cfg)
    assert tr.kernel_plan["flash_ce"] is True
    assert tr.kernel_plan["embed_update"] is True


def test_trainer_kernel_plan_ineligible_falls_back():
    """Multi-device mesh and small batches fall back with a reason —
    never an error (pallas_call does not partition under a mesh)."""
    from predictionio_tpu.parallel.mesh import create_mesh

    u, i, n_users, n_items = _positives()
    cfg = TwoTowerConfig(dim=4, epochs=1, batch_size=256, seed=3,
                         flash_ce_kernel="on", embed_update_kernel="on")
    tr = TwoTowerTrainer((u, i, None), n_users, n_items, cfg,
                         mesh=create_mesh({"data": 8}))
    assert tr.kernel_plan["flash_ce"] is False
    assert "mesh" in tr.kernel_plan["flash_ce_reason"]
    assert tr.kernel_plan["embed_update"] is False

    small = TwoTowerTrainer(
        (u, i, None), n_users, n_items,
        TwoTowerConfig(dim=4, epochs=1, batch_size=64, seed=3,
                       flash_ce_kernel="on"))
    assert small.kernel_plan["flash_ce"] is False
    assert "batch" in small.kernel_plan["flash_ce_reason"]


@pytest.mark.parametrize("flag,batch,tight,form", [
    ("on", 256, False, "one_pass"),
    ("on", 256, True, "two_pass"),     # a chip too small for this dv
    ("off", 256, False, None),
    ("on", 64, False, None),           # ineligible: below MIN_BATCH
], ids=["on", "on_past_the_budget", "off", "ineligible"])
def test_kernel_plan_and_train_report_name_the_backward(monkeypatch, flag,
                                                        batch, tight, form):
    """``flash_ce_backward`` says which backward a capture came from, by
    the kernel's own shape rule; the train report carries the plan."""
    from predictionio_tpu.obs import jaxmon

    if tight:
        monkeypatch.setattr(flash_ce_mod, "_vmem_bytes", lambda: 1 << 16)
    u, i, n_users, n_items = _positives(n=300)
    cfg = TwoTowerConfig(dim=8, epochs=1, batch_size=batch, seed=3,
                         compute_dtype="float32", flash_ce_kernel=flag)
    tr = TwoTowerTrainer((u, i, None), n_users, n_items, cfg)
    assert tr.kernel_plan["flash_ce"] is (form is not None)
    assert tr.kernel_plan["flash_ce_backward"] == form
    monkeypatch.setattr(jaxmon, "TRAINER_REPORTS", {})
    tr.run()
    report = jaxmon.TRAINER_REPORTS["twotower"]
    assert report["kernel_plan"]["flash_ce_backward"] == form


def test_trainer_kernels_end_to_end_match_xla():
    """A full trainer run with BOTH kernels engaged (interpret) tracks
    the XLA-path run epoch-for-epoch in f32 — the integration-level
    equivalence, scan + donation + adagrad included."""
    u, i, n_users, n_items = _positives(n=520, seed=5)
    base = dict(dim=8, epochs=2, batch_size=128, seed=7,
                learning_rate=1e-2, compute_dtype="float32")
    ref = TwoTowerTrainer((u, i, None), n_users, n_items,
                          TwoTowerConfig(**base))
    ker = TwoTowerTrainer((u, i, None), n_users, n_items,
                          TwoTowerConfig(**base, flash_ce_kernel="on",
                                         embed_update_kernel="on"))
    assert ker.kernel_plan["flash_ce"] and ker.kernel_plan["embed_update"]
    l_ref = ref.run()
    l_ker = ker.run()
    np.testing.assert_allclose(l_ker, l_ref, rtol=1e-4, atol=1e-5)
    e_ref = ref.embeddings(l_ref)
    e_ker = ker.embeddings(l_ker)
    np.testing.assert_allclose(e_ker.item_vecs, e_ref.item_vecs,
                               rtol=1e-3, atol=1e-4)


def test_engaged_kernel_compile_failure_raises(monkeypatch):
    """An engaged kernel that the compiler refuses must fail the train
    with the compiler's message — there is no XLA fallback that could
    let a chip run 'pass' on a path nobody chose."""
    import predictionio_tpu.ops.twotower as tt

    def refuse(*args, **kwargs):
        raise ValueError("mosaic said no: block shape (1, 512)")

    monkeypatch.setattr(tt._pl_flash, "pallas_blockwise_ce", refuse)
    u, i, n_users, n_items = _positives()
    cfg = TwoTowerConfig(dim=8, epochs=1, batch_size=128, seed=3,
                         flash_ce_kernel="on")
    tr = tt.TwoTowerTrainer((u, i, None), n_users, n_items, cfg)
    assert tr.kernel_plan["flash_ce"] is True
    with pytest.raises(ValueError, match="mosaic said no"):
        tr.run()
    # and the ops/pallas package offers nothing that turns a failure
    # into False
    assert not hasattr(plk, "probe")


def test_flash_ce_weight_grad_raises_not_zero():
    """The documented nondiff contract: asking for d(loss)/d(weight)
    through the closed-over factory raises loudly instead of silently
    returning zeros (weighted-loss tuning hazard, ops/twotower.py
    _make_blockwise_ce_vjp docstring)."""
    B, D = 128, 8
    u, v, u_idx, i_idx, w = _batch(B, D, seed=8, n_pad=0)

    def loss_of_w(w_):
        fn = make_flash_ce(u_idx, i_idx, w_, 0.07, jnp.float32, B,
                           interpret=True, block=32)
        return fn(u, v)

    with pytest.raises(Exception):  # UnexpectedTracerError on jax 0.4.x
        jax.grad(loss_of_w)(w)


def test_missing_pallas_with_flag_on_raises(monkeypatch):
    """A two-tower module that lost its Pallas kernels must not train
    on the XLA paths when a kernel was asked for: the import is no
    longer guarded (no ``_PALLAS_IMPORT_ERROR`` to degrade on), and a
    trainer without the module raises instead of planning a fallback."""
    import predictionio_tpu.ops.twotower as tt

    assert not hasattr(tt, "_PALLAS_IMPORT_ERROR")
    monkeypatch.setattr(tt, "_pl_flash", None)
    monkeypatch.setattr(tt, "_pl_embed", None)
    u, i, n_users, n_items = _positives()
    cfg = TwoTowerConfig(dim=8, epochs=1, batch_size=128, seed=3,
                         flash_ce_kernel="on", embed_update_kernel="on")
    with pytest.raises(AttributeError):
        tt.TwoTowerTrainer((u, i, None), n_users, n_items, cfg)


# -- chunk_attend: a prefill chunk's latent attention, the walk in one kernel --

from predictionio_tpu.ops import mla as mla_ops     # noqa: E402
from predictionio_tpu.ops.attention import mha_reference     # noqa: E402
from predictionio_tpu.ops.pallas import chunk_attend as chunk_attend_mod  # noqa: E402

#: (d_nope, d_rope, d_v) of the three configurations' mixers, an eighth of
#: them (GLM-5's 192 + 64 | 256; A.X-K1's and LongCat's 128 + 64 | 128), and
#: GLM-5's own: the one whose RoPE key lies inside ``d_nope``'s last lanes
#: (``chunk_attend.key_layout``: zero columns in ``w_uk``)
ATTEND_WIDTHS = {"glm": (24, 8, 32), "axk_longcat": (16, 8, 16),
                 "glm_full_width": (192, 64, 256)}
ATTEND_CHUNK = ATTEND_BLOCK = 16
ATTEND_SLOT = 6 * ATTEND_BLOCK         # positions a slot holds


def attend_case(widths, dtype, heads=4, seed=0):
    """One mixer's weights, a cache of three slots of noise, a chunk's
    queries: ``(dims, p, latents, q)``."""
    dn, dr, dv = widths
    d = mla_ops.MLADims(dim=64, heads=heads, d_nope=dn, d_rope=dr, d_v=dv,
                        q_rank=32, kv_rank=32)
    key = jax.random.PRNGKey(seed)
    p = mla_ops.init(key, d, dtype)
    latents = jax.random.normal(
        jax.random.fold_in(key, 1),
        (3, ATTEND_SLOT, mla_ops.cache_width(d))).astype(dtype)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (ATTEND_CHUNK, heads, dn + dr)).astype(dtype)
    return d, p, latents, q


def some_keep(seed, kept=0.4):
    """A row's own set over a slot, ``[chunk, slot]`` bool: position 0
    always (every row must keep some key it can see)."""
    keep = jax.random.bernoulli(jax.random.PRNGKey(seed), kept,
                                (ATTEND_CHUNK, ATTEND_SLOT))
    return keep.at[:, 0].set(True)


def both_walks(d, p, slot=1):
    """``(kernel, XLA loop)`` of one chunk, each jitted ONCE for every
    offset: ``walk(q, latents, offset, keep) -> [C, H, d_v]`` numpy."""
    def jitted(walk):
        fn = jax.jit(lambda q, lat, at, nb, keep: walk(
            p, d, q, at, lat, slot, nb, ATTEND_BLOCK, keep))
        return lambda q, lat, offset, keep=None: np.asarray(fn(
            q, lat, jnp.int32(offset),
            jnp.int32(-(-(offset + ATTEND_CHUNK) // ATTEND_BLOCK)), keep))

    return jitted(mla_ops.attend_kernel), jitted(mla_ops.attend_blocks)


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "keep"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-3)])
@pytest.mark.parametrize("widths", sorted(ATTEND_WIDTHS))
def test_chunk_attend_is_the_accum_block_loop(widths, dtype, tol, masked):
    """The kernel against ``attend_over_blocks`` over ``expand``
    (``ops/mla.attend_blocks``) at <= 1e-5 in float32; in bfloat16 both
    round keys, values and probabilities at the same places, so what is left
    between them is a float32 sum's order tipping one of those roundings (a
    quarter of a bfloat16 step of the result allowed; three results in a
    thousand differ at all, by 3e-5): with and without a row's own ``keep``,
    at offsets inside a block, on a block's edge and at the slot's last
    chunk."""
    d, p, latents, q = attend_case(
        ATTEND_WIDTHS[widths], dtype,
        heads=2 if widths == "glm_full_width" else 4)
    keep = some_keep(3) if masked else None
    kernel, loop = both_walks(d, p)
    for offset in (0, 7, ATTEND_BLOCK, 43, ATTEND_SLOT - ATTEND_CHUNK):
        got, want = (walk(q, latents, offset, keep) for walk in (kernel, loop))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_chunk_attend_wipes_what_a_row_that_kept_nothing_carried():
    """Rows that keep NOTHING of their first two blocks carry a running
    maximum of ``_NEG`` through them (every masked key then weighs ``exp(0)``);
    the first kept key's ``alpha`` is exactly 0 and wipes it, as
    ``attend_over_blocks`` states: the loop's numbers, and the plain
    softmax's over the kept keys alone."""
    d, p, latents, q = attend_case(ATTEND_WIDTHS["glm"], "float32")
    offset = 4 * ATTEND_BLOCK
    keep = some_keep(5)
    keep = keep.at[::2, :2 * ATTEND_BLOCK].set(False)
    got, want = (walk(q, latents, offset, keep) for walk in both_walks(d, p))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    reach = offset + ATTEND_CHUNK
    k, v = mla_ops.expand(p, d, latents[1:2, :reach, :d.latent])
    plain = mha_reference(q[None], k, v, causal=True, scale=d.softmax_scale,
                          keep=keep[None, :, :reach])[0]
    np.testing.assert_allclose(got, np.asarray(plain), rtol=2e-5, atol=2e-5)


def test_chunk_attend_is_one_program_for_every_reach():
    """``n_blocks``, the offset and the slot are traced: ONE compiled program
    walks 1 block, 2, and the slot's whole length, each the loop's numbers
    (and the next slot's cache, full of huge values, is never read)."""
    d, p, latents, q = attend_case(ATTEND_WIDTHS["axk_longcat"], "float32")
    latents = latents.at[2].set(1e30)
    keep = some_keep(7)

    @jax.jit
    def kernel(q, at, lat, slot, nb, keep):
        return mla_ops.attend_kernel(p, d, q, at, lat, slot, nb,
                                     ATTEND_BLOCK, keep)

    for offset in (0, ATTEND_BLOCK, ATTEND_SLOT - ATTEND_CHUNK):
        nb = -(-(offset + ATTEND_CHUNK) // ATTEND_BLOCK)
        got = kernel(q, jnp.int32(offset), latents, jnp.int32(1),
                     jnp.int32(nb), keep)
        want = mla_ops.attend_blocks(p, d, q, offset, latents, 1, nb,
                                     ATTEND_BLOCK, keep)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert kernel._cache_size() == 1


INDEXED = mla_ops.MLADims(
    dim=64, heads=4, d_nope=24, d_rope=8, d_v=32, q_rank=32, kv_rank=32,
    scale_q=False, scale_kv=False, index_heads=4, index_dim=32, index_topk=24)


@pytest.mark.parametrize("dims", [
    INDEXED, mla_ops.MLADims(dim=64, heads=4, d_nope=16, d_rope=8, d_v=16,
                             q_rank=32, kv_rank=32)],
    ids=["index", "no_index"])
def test_prefill_chunk_through_the_kernel_is_the_plain_form(dims):
    """A history prefilled chunk by chunk through the kernel against
    ``attend_full``, every position against every earlier one with its scores
    materialised: below ``index_topk`` (the first chunk: all in reach
    attended, nothing scored), above it (each row under its own set), and a
    LAST chunk whose rows past the history's end are padding full of huge
    values, which no real row reads."""
    chunk, n = 16, 56           # three and a half chunks
    key = jax.random.PRNGKey(11)
    p = mla_ops.init(key, dims, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (n, 64), jnp.float32)
    want = mla_ops.attend_full(p, dims, x[None], jnp.arange(n)[None])[0]
    padded = jnp.concatenate([x, jnp.full((8, 64), 1e4, jnp.float32)])
    cache = mla_ops.init_cache(dims, 2, 64, jnp.float32)
    step = jax.jit(lambda x, at, cache: mla_ops.prefill_chunk(
        p, dims, x, at, cache, 1, chunk))
    scanned = []
    for at in range(0, n, chunk):
        out, cache, blocks = step(padded[at:at + chunk], jnp.int32(at),
                                  cache)
        real = min(chunk, n - at)
        assert np.isfinite(np.asarray(out[:real])).all()
        np.testing.assert_allclose(np.asarray(out[:real]),
                                   np.asarray(want[at:at + real]),
                                   rtol=2e-4, atol=2e-4)
        scanned.append(int(blocks))
    assert step._cache_size() == 1
    assert scanned == ([0, 2, 3, 4] if dims.has_index else [0] * 4)


@pytest.mark.parametrize("d_nope,d_rope,rope_at,wide", [
    (192, 64, 128, 256),     # GLM-5: zero columns at lanes 128-191 of w_uk
    (128, 64, 128, 128),     # A.X-K1, LongCat: behind d_nope, none needed
    (24, 8, 24, 24)])        # under one row of lanes: behind d_nope
def test_a_keys_rope_part_lies_at_a_whole_row_of_lanes(d_nope, d_rope,
                                                       rope_at, wide):
    assert chunk_attend_mod.key_layout(d_nope, d_rope) == (rope_at, wide)
    x = jnp.arange(d_nope, dtype=jnp.float32)[None]
    r = -jnp.ones((1, d_rope), jnp.float32)
    laid = chunk_attend_mod.laid_out(x, r, rope_at)[0]
    assert (laid[rope_at:rope_at + d_rope] == -1).all()
    assert sorted(laid[laid >= 0].tolist()) == list(range(d_nope))


#: SHA-256 of each path's jaxpr text at PR 50's parent (tests/other_walks.py);
#: ``gqa.block_step`` since PR 56 the BLOCK-DIFFUSION forward's (a block length
#: of 4), at PR 56's parent: a causal stack's extension left that walk then
#: (``gqa.extend`` below, against ``attend_full``)
PARENTS_WALKS = {
    "mla.extend": "0b359426918a157f15a9c65f75c8809f3599bbfcdb30927e5344051d742d859c",
    "mla.extend.index": "45fca96ff67e1f26c11f8ad643b93c76c9da03f5063cae1d3ceec80afeda2045",
    "gqa.prefill_chunk": "ab1236eca7a46f8086370d687b2b69bd3dcd9976d71902098f2b92e4502ab021",
    "gqa.block_step": "ccafb0c553bae6edd088fbcd1ea39e16239d93b68750dffea9dec57028b79b65",
    "gqa.window_prefill_chunk": "ae86742bcf15b1c7dbc14ca7383e34a7b30c6ae5a5b7d3d9f59cdd0713e1f0e6",
    "gqa.window_extend": "c451dba88c8a22edb921048e63f47c577a3f33ae804c246115d618e3d04b8348",
}


@pytest.fixture(scope="module")
def walks_digests():
    from tests import other_walks

    return other_walks.digests()


@pytest.mark.parametrize("path", sorted(PARENTS_WALKS))
def test_the_walks_the_kernel_does_not_serve_trace_to_the_parents_jaxpr(
        walks_digests, path):
    """The absorbed extension, grouped-query attention's chunks and
    block-diffusion forwards and the window walk still fold their blocks with
    ``_accum_block``: the same primitives in the same order as at the commit
    before the kernel that might have taken them, letter for letter, and not
    one ``pallas_call`` among them."""
    assert walks_digests[path] == PARENTS_WALKS[path]


def test_no_walk_the_kernels_leave_alone_holds_a_pallas_call():
    from tests import other_walks

    assert not [name for name, text in other_walks.jaxprs().items()
                if "pallas_call" in text]


# -- span_walk: an extension's rows, each over its own blocks (PR 56) ----------

from predictionio_tpu.ops import gqa as gqa_ops     # noqa: E402

WALK_BLOCK, WALK_SLOT = 8, 64
#: the three cells' head shapes at a model width of 64 (differential pairs of
#: 64-wide heads two to a key head; 128-wide heads four to one; keys of 192
#: beside values of 128 sixteen to one), the pairs at another width, plain
#: heads of 64, and the tests' own small odd widths
WALK_DIMS = {
    "phi": dict(heads=8, kv_heads=4, head_dim=64, diff=True, bias=True,
                rope=False, qk_norm=False),
    "granite": dict(heads=8, kv_heads=2, head_dim=128, rope=False,
                    qk_norm=False, scale=0.0078125),
    "mimo": dict(heads=32, kv_heads=2, head_dim=192, v_head_dim=128,
                 rope_dims=64, qk_norm=False, value_scale=0.707),
    "pairs_of_128": dict(heads=16, kv_heads=4, head_dim=128, v_head_dim=64,
                         diff=True, rope=False),
    "plain_64": dict(heads=4, kv_heads=2, head_dim=64, v_head_dim=128),
    "small": dict(heads=8, kv_heads=2, head_dim=24, v_head_dim=16,
                  rope_dims=8, qk_norm=False),
}


def walk_case(name, dtype, seed=0, **more):
    """``(dims, p, a span of four slots of noise)``; slot 3 is a batch's
    scratch slot and holds huge values."""
    d = gqa_ops.GQADims(dim=64, block_len=1, **dict(WALK_DIMS[name], **more))
    key = jax.random.PRNGKey(seed)
    span = jax.random.normal(jax.random.fold_in(key, 1),
                             (4, WALK_SLOT, d.cache_width)).astype(dtype)
    return d, gqa_ops.init(key, d, dtype), span.at[3].set(1e30)


def own_blocks(pos0, S, real=True):
    """A row's own rounds, by ``StackPrograms._extend_fn``'s rule."""
    return -(-(pos0 + S) // WALK_BLOCK) if real else 0


@pytest.mark.parametrize("S", [1, 4], ids=["cross_row", "extend_len"])
@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-3)])
@pytest.mark.parametrize("name", sorted(WALK_DIMS))
def test_span_walk_is_the_accum_block_loop_over_each_rows_own_blocks(
        name, dtype, tol, S):
    """The kernel against ``attend_over_blocks`` (``ops/gqa._attend``, every
    row as far as the batch's longest): rows of unequal reach in one batch,
    one whose reach ends ON a block's edge, one that starts its slot, and a
    padding row on the scratch slot (no rounds: zeros, whatever the slot
    holds). Each real row walks ITS OWN blocks: whole blocks past them hold
    NaN here, which the loop over the longest row's would multiply."""
    d, p, span = walk_case(name, dtype)
    starts = [37, 2 * WALK_BLOCK - S, 0, 5]
    slots = jnp.array([1, 2, 0, 3])
    counts = [own_blocks(at, S) for at in starts[:3]] + [0]
    pos = jnp.array(starts)[:, None] + jnp.arange(S)[None]
    q = jax.random.normal(jax.random.PRNGKey(7), (4, S, d.heads, d.head_dim))
    want = jax.jit(lambda q, span: gqa_ops._attend(
        d, q, pos, span, slots, jnp.int32(max(counts)), WALK_BLOCK))(q, span)
    for b, n in enumerate(counts[:3]):      # never read: nothing to poison
        span = span.at[int(slots[b]), n * WALK_BLOCK:].set(jnp.nan)
    got = jax.jit(lambda q, span, n: gqa_ops._walk(
        d, q, pos, span, slots, n, WALK_BLOCK))(q, span, jnp.array(counts))
    assert got.shape == want.shape and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got[:3]), np.asarray(want[:3]),
                               rtol=tol, atol=tol)
    assert not np.asarray(got[3]).any()


@pytest.mark.parametrize("name", ["phi", "mimo"])
def test_span_walk_is_one_program_for_every_batch(name):
    """Slots, positions and round counts are traced: ONE compiled program
    serves one real row alone beside two padding rows, then three rows, then
    the same rows with one count for all (the batch's longest: the blocks
    past a row's reach are masked, the numbers the same)."""
    d, p, span = walk_case(name, "float32")
    S = 4
    q = jax.random.normal(jax.random.PRNGKey(9), (3, S, d.heads, d.head_dim))

    @jax.jit
    def walk(q, pos, span, slots, counts):
        return gqa_ops._walk(d, q, pos, span, slots, counts, WALK_BLOCK)

    def loop(pos, slots, n):
        return gqa_ops._attend(d, q, pos, span, slots, jnp.int32(n),
                               WALK_BLOCK)

    def at(starts):
        return jnp.array(starts)[:, None] + jnp.arange(S)[None]

    alone = walk(q, at([43, 0, 0]), span, jnp.array([2, 3, 3]),
                 jnp.array([own_blocks(43, S), 0, 0]))
    np.testing.assert_allclose(
        np.asarray(alone[0]), np.asarray(loop(at([43, 0, 0]), jnp.array(
            [2, 3, 3]), 6)[0]), rtol=1e-5, atol=1e-5)
    assert not np.asarray(alone[1:]).any()
    starts, slots = [43, 9, 20], jnp.array([2, 0, 1])
    want = loop(at(starts), slots, 6)
    for counts in ([own_blocks(a, S) for a in starts], [6, 6, 6]):
        got = walk(q, at(starts), span, slots, jnp.array(counts))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    assert walk._cache_size() == 1


def test_a_span_that_is_not_whole_rows_of_lanes_is_refused_on_a_tpu():
    """No ``GQADims`` falls back to the loop: every causal stack's extension
    walks in the kernel, under the interpreter at any width. Compiled, the
    copy of a block takes whole 128-lane rows of the span (Mosaic's slice of
    a tiled array): the three configurations' rows are 2,560, 1,280 and 2,048
    wide; a test-sized row of 80 names the reason instead of the compiler."""
    from predictionio_tpu.ops.pallas import span_walk as span_walk_mod

    d, p, span = walk_case("small", "float32")
    assert d.cache_width == 80
    q = jnp.zeros((1, len(gqa_ops.walk_groups(d)), 4, d.head_dim))
    with pytest.raises(ValueError, match="128"):
        span_walk_mod.span_walk(
            q, jnp.zeros((1, 4, 1), jnp.int32), span, jnp.array([0]),
            jnp.array([1]), groups=gqa_ops.walk_groups(d), block=WALK_BLOCK,
            scale=1.0, interpret=False)


@pytest.mark.parametrize("name", ["phi", "mimo", "small"])
def test_gqa_extend_is_the_plain_form(name):
    """What ``PARENTS_WALKS["gqa.block_step"]`` pinned until PR 56, held to
    what it computes: a history prefilled chunk by chunk, then EXTENDED in
    batches (two sessions of unequal reach and a padding row; 4, 3 and 1 new
    positions a row) against ``attend_full``, every position against every
    earlier one with its scores materialised; under the differential dims a
    cross mixer's rows over the same span as well (``cross_rows``)."""
    d, p, _ = walk_case(name, "float32")
    C, n = 16, 45
    x = jax.random.normal(jax.random.PRNGKey(3), (2, n, 64), jnp.float32)
    pos = jnp.arange(n, dtype=jnp.int32)
    want = [gqa_ops.attend_full(p, d, x[i], pos) for i in (0, 1)]
    cache = jnp.zeros((4, WALK_SLOT, d.cache_width), jnp.float32)
    held = (32, 16)     # whole chunks: session 0 in slot 1, session 1 in 2
    for i, slot in ((0, 1), (1, 2)):
        for at in range(0, held[i], C):
            out, cache = gqa_ops.prefill_chunk(
                p, d, x[i, at:at + C], jnp.int32(at), cache, slot, WALK_BLOCK)
            np.testing.assert_allclose(np.asarray(out), np.asarray(
                want[i][at:at + C]), rtol=2e-4, atol=2e-4)
    step = jax.jit(lambda rows, pos, cache, slots, counts: gqa_ops.extend(
        p, d, rows, pos, cache, slots, counts, WALK_BLOCK))
    at, S = list(held), 4
    for new in ((4, 3), (1, 4), (3, 2)):
        rows = jnp.zeros((3, S, 64), jnp.float32)
        for i in (0, 1):
            rows = rows.at[i, :new[i]].set(x[i, at[i]:at[i] + new[i]])
        out, cache = step(
            rows, jnp.array(at + [0])[:, None] + jnp.arange(S)[None], cache,
            jnp.array([1, 2, 3]),
            jnp.array([own_blocks(a, S) for a in at] + [0]))
        for i in (0, 1):
            np.testing.assert_allclose(
                np.asarray(out[i, :new[i]]),
                np.asarray(want[i][at[i]:at[i] + new[i]]),
                rtol=2e-4, atol=2e-4)
            at[i] += new[i]
    assert step._cache_size() == 1
    if not d.diff:
        return
    cross = gqa_ops.GQADims(dim=64, block_len=1, cross=True,
                            **WALK_DIMS[name])
    pc = gqa_ops.init(jax.random.PRNGKey(5), cross)
    last = jnp.array([a - 1 for a in at])
    got = gqa_ops.cross_rows(
        pc, cross, x[jnp.arange(2), last], last, cache, jnp.array([1, 2]),
        jnp.array([own_blocks(int(a), 1) for a in last]), WALK_BLOCK,
        depth=3)
    for i in (0, 1):
        whole = gqa_ops.attend_full(
            pc, cross, x[i, :at[i]], pos[:at[i]], 3,
            kv=gqa_ops.project(p, d, x[i, :at[i]], pos[:at[i]])[1:])
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(whole[-1]),
                                   rtol=2e-4, atol=2e-4)
