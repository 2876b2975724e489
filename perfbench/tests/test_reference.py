"""The plain reference and the layout maps, against loops written out
cell by cell in float64 numpy."""

import numpy as np
import pytest

from perfbench import harness

fam = harness.load_module(harness.os.path.join(
    harness.HERE, "families", "diffusion3d.py"), "perfbench_family_diffusion3d")
PARAMS = {"lam": 1.0, "cp_min": 1.0, "lx": 10.0, "ly": 10.0, "lz": 10.0}


@pytest.mark.parametrize("periods", [(0, 0, 0), (1, 1, 1), (1, 0, 1)])
@pytest.mark.parametrize("dims", [(1, 1, 1), (2, 2, 1), (3, 1, 2)])
def test_layout_maps(dims, periods):
    g = fam.Geometry((6, 5, 7), dims, periods)
    for d in range(3):
        own, to_ref = g.owner(d), g.blocked_to_ref(d)
        assert len(own) == g.global_size(d)
        assert len(set(own)) == len(own)
        np.testing.assert_array_equal(to_ref[own], np.arange(len(own)))
        n = g.local[d]
        for b in range(dims[d]):          # halo planes copy a neighbour
            lo, hi = b * n, b * n + n - 1
            if periods[d] or b > 0:
                assert to_ref[lo] == to_ref[(lo - 2) % len(to_ref)]
            if periods[d] or b < dims[d] - 1:
                assert to_ref[hi] == to_ref[(hi + 2) % len(to_ref)]


def loops(T, Cp, periods, dx, dt, lam, n):
    T = T.astype(np.float64).copy()
    N = T.shape
    for _ in range(n):
        new = T.copy()
        for i in range(N[0]):
            for j in range(N[1]):
                for k in range(N[2]):
                    idx = (i, j, k)
                    if any(not periods[d] and idx[d] in (0, N[d] - 1)
                           for d in range(3)):
                        continue                 # frozen open edge
                    div = 0.0
                    for d in range(3):
                        up, dn = list(idx), list(idx)
                        up[d] = (idx[d] + 1) % N[d]
                        dn[d] = (idx[d] - 1) % N[d]
                        q_up = -lam * (T[tuple(up)] - T[idx]) / dx[d]
                        q_dn = -lam * (T[idx] - T[tuple(dn)]) / dx[d]
                        div += (q_up - q_dn) / dx[d]
                    new[idx] = T[idx] + dt * (-div) / Cp[idx]
        T = new
    return T


@pytest.mark.parametrize("periods", [(0, 0, 0), (1, 1, 1), (0, 1, 0)])
def test_reference_matches_loops(periods):
    g = fam.Geometry((7, 6, 5), (1, 1, 1), periods)
    T, Cp = fam.make_fields(g, PARAMS, 2**31 + 11, blocked=False)
    T, Cp = np.asarray(T), np.asarray(Cp)
    got = np.asarray(fam.reference_steps(g, PARAMS, 3)(T, Cp))
    want = loops(T, Cp, periods, fam.spacing(g, PARAMS),
                 fam.timestep(g, PARAMS), PARAMS["lam"], 3)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(
        want).max())
    assert np.abs(got - T).max() > 1e-3 * np.abs(T).max()   # it moved


def test_fields_from_the_seed():
    g = fam.Geometry((8, 8, 8), (2, 1, 1), (1, 0, 0))
    a = [np.asarray(x) for x in fam.make_fields(g, PARAMS, 5, blocked=True)]
    b = [np.asarray(x) for x in fam.make_fields(g, PARAMS, 5, blocked=True)]
    c = [np.asarray(x) for x in fam.make_fields(g, PARAMS, 6, blocked=True)]
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[0], c[0])
    ref = [np.asarray(x) for x in fam.make_fields(g, PARAMS, 5,
                                                   blocked=False)]
    for blocked, r in zip(a, ref):   # blocked layout = reference, halos too
        np.testing.assert_array_equal(
            blocked, r[np.ix_(*(g.blocked_to_ref(d) for d in range(3)))])
    assert a[1].min() >= PARAMS["cp_min"]


def test_one_fields_program_for_every_seed():
    import jax

    compiles = []

    def listen(name, *a, **k):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        g = fam.Geometry((8, 8, 8), (1, 1, 1), (0, 0, 0))
        fam.make_fields(g, PARAMS, 2**31 + 1, blocked=False)
        before = len(compiles)
        assert before >= 1          # the listener sees compiles
        for seed in (2**31 + 2, 3, 4_000_000_000):
            fam.make_fields(g, PARAMS, seed, blocked=False)
        assert len(compiles) == before
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
