"""Several accelerators under ONE ``Context`` (``Context(accelerators=g)``,
DPLASMA's ``-g``): one scheduler, one device module per chip, a device
chosen per task, tiles advised onto the chips and moved chip to chip by
the runtime.  Counts and results on the CPU backend's virtual devices, a
case each; never a time."""

import threading

import jax
import numpy as np
import pytest

from parsec_tpu import Context
from parsec_tpu.core.lifecycle import AccessMode, DEV_CPU, DEV_TPU, HookReturn
from parsec_tpu.data.data import BeingOverwritten, Coherency, data_create
from parsec_tpu.datadist import TiledMatrix, advise_data_on_devices
from parsec_tpu.device import device as devmod
from parsec_tpu.ops.cholesky import cholesky_ptg

NB = 16
TILE = NB * NB * 4
GRID = (2, 2)


def _accs(ctx):
    return [d for d in ctx.devices if d.device_type == DEV_TPU]


def _spd(nt, seed=5):
    n = nt * NB
    M = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
    return M @ M.T + n * np.eye(n, dtype=np.float32)


def _matrix(S):
    n = S.shape[0]
    A = TiledMatrix(n, n, NB, NB, name="A", dtype=np.float32)
    for i in range(A.mt):
        for j in range(i + 1):
            tile = S[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB].copy()
            d = A.data_of(i, j)
            (d.get_copy(0) or d.attach_copy(0, tile)).payload = tile
    return A


def _lower(A):
    L = np.zeros((A.m, A.n), np.float32)
    for i in range(A.mt):
        for j in range(i + 1):
            L[i * NB:(i + 1) * NB, j * NB:(j + 1) * NB] = np.asarray(
                A.data_of(i, j).newest_copy().payload)
    return np.tril(L)


def _solve(nt, g, before=None):
    """The lower dpotrf of ``nt x nt`` tiles under ``g`` accelerators:
    the factor, each module's counters, the context's, the matrix."""
    S = _spd(nt)
    A = _matrix(S)
    ctx = Context(nb_cores=2, accelerators=g)
    try:
        accs = _accs(ctx)
        advised = None
        if g > 1:
            advised = advise_data_on_devices(A, accs, GRID, uplo="lower")
        if before is not None:
            before(ctx, A)
        tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=nt, A=A)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=300)
        ctx.flush()
        L = _lower(A)
        host = {k: np.asarray(A.data_of(*k).get_copy(0).payload)
                for k in A.materialized_keys()}
    finally:
        ctx.fini()
    return dict(L=L, S=S, A=A, host=host, advised=advised,
                stats=[dict(d.stats) for d in accs], placed=dict(ctx.stats),
                jdevs=[d.jdev for d in accs], names=[d.name for d in accs])


@pytest.fixture(scope="module", params=[6, 8], ids=["nt6", "nt8"])
def pair(request):
    nt = request.param
    return nt, _solve(nt, 1), _solve(nt, 4)


def _shares(nt, p, q):
    share = [0] * (p * q)
    for k in range(nt):
        share[(k % p) * q + k % q] += 1
        for m in range(k + 1, nt):
            share[(m % p) * q + k % q] += 1
            share[(m % p) * q + m % q] += 1
            for n in range(k + 1, m):
                share[(m % p) * q + n % q] += 1
    return share


def test_the_factor_is_the_one_accelerator_factor_bit_for_bit(pair):
    _nt, one, four = pair
    np.testing.assert_array_equal(one["L"], four["L"])


def test_the_factor_is_lapacks_within_tolerance(pair):
    _nt, _one, four = pair
    want = np.linalg.cholesky(four["S"].astype(np.float64))
    np.testing.assert_allclose(four["L"], want, rtol=0, atol=2e-4)


def test_four_modules_on_four_distinct_chips(pair):
    _nt, one, four = pair
    assert four["names"] == ["tpu1", "tpu2", "tpu3", "tpu4"]
    assert four["jdevs"] == jax.local_devices()[:4]
    assert one["names"] == ["tpu1"] and one["jdevs"] == jax.local_devices()[:1]


def test_each_module_executed_exactly_its_advised_share(pair):
    nt, _one, four = pair
    assert [s["executed_tasks"] for s in four["stats"]] == _shares(nt, *GRID)
    assert sum(_shares(nt, *GRID)) == nt + nt * (nt - 1) \
        + nt * (nt - 1) * (nt - 2) // 6
    # the advice itself: every lower tile, 2D-cyclic
    tiles = [0] * 4
    for m in range(nt):
        for n in range(m + 1):
            tiles[(m % 2) * 2 + n % 2] += 1
    assert list(four["advised"].values()) == tiles
    assert list(four["advised"]) == [1, 2, 3, 4]


def test_every_task_was_placed_by_the_tile_it_writes(pair):
    nt, one, four = pair
    placed = four["placed"]
    ntasks = sum(_shares(nt, *GRID))
    assert placed["selected_by_owner"] + placed["selected_by_advice"] == ntasks
    assert placed["selected_by_bytes"] == placed["selected_by_load"] == 0
    # a tile's first writer goes by the advice, every later one by owner
    assert placed["selected_by_advice"] == nt * (nt + 1) // 2
    # with one accelerator nothing is chosen
    assert set(one["placed"].values()) == {0}


def test_the_matrix_crosses_the_host_once_each_way(pair):
    nt, one, four = pair
    lower = nt * (nt + 1) // 2 * TILE
    for side in (one, four):
        assert sum(s["bytes_in"] for s in side["stats"]) == lower
        assert sum(s["bytes_out"] for s in side["stats"]) == lower
    # and is at home, every tile its last version
    for (i, j), tile in four["host"].items():
        np.testing.assert_array_equal(
            np.tril(tile) if i == j else tile,
            four["L"][i * NB:(i + 1) * NB, j * NB:(j + 1) * NB])


def test_tiles_move_chip_to_chip_on_every_chip(pair):
    _nt, one, four = pair
    for s in four["stats"]:
        assert s["bytes_d2d"] > 0 and s["d2d_tiles"] * TILE == s["bytes_d2d"]
    assert one["stats"][0]["bytes_d2d"] == one["stats"][0]["d2d_tiles"] == 0


def test_no_fallback_ran_and_every_output_was_written_in_place(pair):
    nt, _one, four = pair
    for s in four["stats"]:
        for k in ("wave_fallbacks", "submit_retries", "stage_batch_fallbacks",
                  "donation_refused", "peer_holds_refused", "evict_dirty",
                  "commits_home_unknown", "commits_donate_unknown"):
            assert s[k] == 0, k
    assert sum(s["tile_args_donated"] for s in four["stats"]) \
        == sum(_shares(nt, *GRID))


# -- attach ---------------------------------------------------------------

def test_one_accelerator_leaves_the_context_as_it_was():
    with Context(nb_cores=1) as ctx, Context(nb_cores=1,
                                             accelerators=1) as same:
        for c in (ctx, same):
            assert [d.name for d in c.devices] == ["cpu0", "tpu1"]
            dev = c.devices[1]
            assert dev.peers == [] and dev._thread is None
            assert dev._place is None and dev.data_index == 1
            assert dev.jdev == jax.local_devices()[0]  # (by the rank: 0)
            assert set(c.stats.values()) == {0}


def test_the_rank_binds_the_one_module_as_ever():
    with Context(nb_cores=1, rank=3, nranks=1) as ctx:
        assert ctx.devices[1].jdev == jax.local_devices()[3]


@pytest.mark.parametrize("g", [2, 4])
def test_g_accelerators_attach_g_modules_in_order(g):
    with Context(nb_cores=1, accelerators=g) as ctx:
        assert [d.name for d in ctx.devices] \
            == ["cpu0"] + [f"tpu{i}" for i in range(1, g + 1)]
        accs = _accs(ctx)
        assert [d.jdev for d in accs] == jax.local_devices()[:g]
        assert [d.index for d in accs] == [d.data_index for d in accs] \
            == list(range(1, g + 1))
        for d in accs:
            assert d.peers == [p for p in accs if p is not d]
            assert d._thread.is_alive()
        # each its own residency, lanes, committer slot, program cache
        for attr in ("_res", "_h2d", "_wb", "_jit_cache", "stats"):
            assert len({id(getattr(d, attr)) for d in accs}) == g
        threads = [d._thread for d in accs]
    assert not any(t.is_alive() for t in threads)  # fini walks all of them


def test_several_ranks_take_their_slice_of_the_chips():
    with Context(nb_cores=1, rank=1, nranks=2, accelerators=2) as ctx:
        assert [d.jdev for d in _accs(ctx)] == jax.local_devices()[2:4]
        assert not any(d._may_donate for d in _accs(ctx))


@pytest.mark.parametrize("g, error", [(9, RuntimeError), (0, ValueError),
                                      (-1, ValueError)])
def test_more_chips_than_there_are_raises_at_construction(g, error):
    with pytest.raises(error):
        Context(nb_cores=1, accelerators=g)


# -- the choice of a device -----------------------------------------------

class _Dev:
    device_type = DEV_TPU
    enabled = True

    def __init__(self, index, load=0.0, resident=0):
        self.index, self.device_load, self._resident = index, load, resident
        self.added = 0.0

    def resident_data(self, task):
        return self._resident

    def time_estimate(self, task):
        return 1.0

    def add_load(self, dt):
        self.added += dt


class _Cpu(_Dev):
    device_type = DEV_CPU


class _Chore:
    enabled, evaluate, time_estimate = True, None, None

    def __init__(self, device_type):
        self.device_type = device_type


def _ask(devices, specs, chores=(DEV_TPU,)):
    class Ctx:
        pass
    ctx = Ctx()
    ctx.devices = devices
    ctx.stats = {k: 0 for k in ("selected_by_owner", "selected_by_advice",
                                "selected_by_bytes", "selected_by_load")}
    task = type("T", (), {})()
    task.task_class = type("TC", (), {"chores": [_Chore(t) for t in chores]})
    task.chore_mask = ~0
    task.body_args = specs
    task.prof = {}
    assert devmod.select_best_device(ctx, task) is HookReturn.DONE
    return task.selected_device, ctx.stats


def _tile(owner=-1, preferred=-1):
    d = data_create((0, 0), payload=np.zeros((2, 2), np.float32))
    d.owner_device, d.preferred_device = owner, preferred
    return d


RW, RD = AccessMode.INOUT, AccessMode.IN


def test_choice_1_the_owner_of_the_written_tile():
    devs = [_Dev(1), _Dev(2, load=-5.0, resident=99), _Dev(3)]
    # (the first WRITTEN flow decides, not the first flow; the owner goes
    # before the advice, the bytes and the load)
    specs = [("data", _tile(owner=2, preferred=2), RD),
             ("data", _tile(owner=3, preferred=1), RW),
             ("value", 7, AccessMode.VALUE)]
    dev, by = _ask(devs, specs)
    assert dev is devs[2] and by["selected_by_owner"] == 1
    assert sum(by.values()) == 1 and dev.added == 1.0


def test_choice_2_the_advice_where_no_accelerator_owns_it():
    devs = [_Dev(1), _Dev(2, resident=99), _Dev(3)]
    for owner in (0, -1, 7):  # the host, nobody, a device not eligible
        dev, by = _ask(devs, [("data", _tile(owner=owner, preferred=1), RW),
                              ("data", _tile(owner=2, preferred=2), RD)])
        assert dev is devs[0] and by["selected_by_advice"] == 1
        assert sum(by.values()) == 1


def test_choice_3_most_of_the_inputs_bytes_failing_a_written_flow():
    devs = [_Dev(1, resident=10), _Dev(2, resident=30), _Dev(3, load=-9.0)]
    dev, by = _ask(devs, [("data", _tile(owner=1, preferred=1), RD)])
    assert dev is devs[1] and by["selected_by_bytes"] == 1
    # ... and a written tile that nobody owns or is advised
    dev, by = _ask(devs, [("data", _tile(), RW)])
    assert dev is devs[1] and by["selected_by_bytes"] == 1


def test_choice_4_the_least_load_failing_all_of_that():
    devs = [_Dev(1, load=3.0), _Dev(2, load=1.0), _Dev(3, load=2.0)]
    dev, by = _ask(devs, [("data", _tile(), RW)])
    assert dev is devs[1] and by["selected_by_load"] == 1
    assert sum(by.values()) == 1
    # an opaque payload (a DTD comm task's raw tuple) says nothing either
    dev, by = _ask(devs, ("raw", 1))
    assert dev is devs[1] and by["selected_by_load"] == 1


def test_one_eligible_device_returns_before_any_of_it():
    class Blind(_Dev):
        def resident_data(self, task):
            raise AssertionError("asked")

    dev, by = _ask([_Cpu(0), Blind(1)],
                   [("data", _tile(owner=1, preferred=1), RW)])
    assert dev.index == 1 and set(by.values()) == {0}


def test_one_accelerator_beside_a_cpu_chore_chooses_as_it_did():
    cpu, acc = _Cpu(0), _Dev(1)
    both = (DEV_CPU, DEV_TPU)
    # 0. an input advised to a device; 1. affinity; 2. the least ETA
    dev, by = _ask([cpu, acc], [("data", _tile(preferred=0), RD)], both)
    assert dev is cpu and set(by.values()) == {0}
    acc._resident = 5
    dev, _ = _ask([cpu, acc], [("data", _tile(), RD)], both)
    assert dev is acc
    acc._resident, acc.device_load = 0, 10.0
    dev, _ = _ask([cpu, acc], [("data", _tile(), RD)], both)
    assert dev is cpu


# -- coherence between chips ------------------------------------------------

def test_a_planted_stale_peer_copy_is_never_read():
    """Every tile gets, on a chip that is NOT advised it, a copy of an
    older version full of NaN: a walk that took it for current would
    poison the factor."""
    planted = []

    def plant(ctx, A):
        accs = _accs(ctx)
        for (m, n) in A.materialized_keys():
            data = A.data_of(m, n)
            data.get_copy(0).version = 1
            wrong = accs[(data.preferred_device) % 4]  # the next chip
            junk = jax.device_put(
                np.full((NB, NB), np.nan, np.float32), wrong.jdev)
            c = data.attach_copy(wrong.data_index, junk)
            c.version = 0
            planted.append((data, wrong.data_index))

    got = _solve(6, 4, before=plant)
    assert np.isfinite(got["L"]).all()
    np.testing.assert_array_equal(got["L"], _solve(6, 4)["L"])
    assert planted and sum(s["bytes_in"] for s in got["stats"]) \
        == 21 * TILE == sum(s["bytes_out"] for s in got["stats"])
    # every planted copy was superseded by a peer's commit and dropped
    # there, or is still the stale thing it was: never current
    for data, idx in planted:
        c = data.get_copy(idx)
        assert c is None or c.version < data.newest_copy().version \
            or not np.isnan(np.asarray(c.payload)).any()


def _two_chips():
    ctx = Context(nb_cores=1, accelerators=2)
    return ctx, _accs(ctx)


def _resident(dev, data, value, version, dirty):
    """A copy of ``data`` on ``dev`` as a commit or a landing leaves it."""
    arr = jax.device_put(np.full((NB, NB), value, np.float32), dev.jdev)
    with dev._res.lock:
        dev._res.account(data, arr.nbytes)
        c = data.attach_copy(dev.data_index, arr)
        c.version = version
        c.coherency = Coherency.OWNED if dirty else Coherency.SHARED
        dev._res.touch(data, dirty=dirty)
    return arr


def test_a_clean_peer_copy_evicted_writes_nothing_home():
    ctx, (a, b) = _two_chips()
    try:
        data = data_create("x", payload=np.zeros((NB, NB), np.float32))
        _resident(a, data, 3.0, 2, dirty=True)     # the newest version
        _resident(b, data, 3.0, 2, dirty=False)    # its clean copy
        assert b._res.used == TILE
        with b._res.lock:
            b._res._evict(TILE)
        assert b.stats["evictions"] == b.stats["evict_clean"] == 1
        assert b.stats["evict_dirty"] == b.stats["evict_bytes_home"] == 0
        assert b.stats["bytes_out"] == 0 and b._res.used == 0
        assert data.get_copy(b.data_index) is None
        assert data.get_copy(0).version == 0       # nothing went home
        # the owner's own eviction does write it home, once
        with a._res.lock:
            a._res._evict(TILE)
        assert a.stats["evict_dirty"] == 1 and a.stats["bytes_out"] == TILE
        assert data.get_copy(0).version == 2
        assert float(np.asarray(data.get_copy(0).payload)[0, 0]) == 3.0
    finally:
        ctx.fini()


def test_a_commit_on_one_chip_drops_the_peers_copies_of_the_tile():
    ctx, (a, b) = _two_chips()
    try:
        data = data_create("x", payload=np.zeros((NB, NB), np.float32))
        other = data_create("y", payload=np.zeros((NB, NB), np.float32))
        _resident(a, data, 1.0, 1, dirty=True)
        _resident(b, data, 1.0, 1, dirty=False)
        _resident(b, other, 5.0, 1, dirty=False)   # (current: it stays)
        new = jax.device_put(np.full((NB, NB), 2.0, np.float32), a.jdev)
        task = type("T", (), {})()
        with a._res.lock:
            a._commit_output(data, new, new.nbytes, False)
        a._supersede([(task, [], [(0, data), (0, other)])])
        assert b.stats["peer_copies_dropped"] == 1
        assert data.get_copy(b.data_index) is None
        assert other.get_copy(b.data_index) is not None
        assert b._res.used == TILE and b.stats["evictions"] == 0
        assert b.stats["bytes_out"] == 0 and data.get_copy(0).version == 0
        assert data.owner_device == a.data_index
        assert data.newest_copy().payload is new
    finally:
        ctx.fini()


def test_a_landing_takes_the_peers_copy_and_never_the_hosts():
    ctx, (a, b) = _two_chips()
    try:
        data = data_create("x", payload=np.zeros((NB, NB), np.float32))
        arr = _resident(a, data, 4.0, 1, dirty=True)
        # the version has gone home too (a last version): the host holds
        # it at the same version, and a peer still reads it from the chip
        host = data.attach_copy(0, np.full((NB, NB), 4.0, np.float32))
        host.version = 1
        got = b._h2d.one(data)
        assert got.devices() == {b.jdev} and float(got[0, 0]) == 4.0
        assert b.stats["bytes_d2d"] == TILE and b.stats["d2d_tiles"] == 1
        assert b.stats["bytes_in"] == 0 and data.peer_holds == 0
        assert data.get_copy(a.data_index).payload is arr
        assert data.get_copy(b.data_index).version == 1
    finally:
        ctx.fini()


def test_a_peers_hold_refuses_the_donation_and_a_claim_refuses_the_peer():
    data = data_create("x", payload=np.zeros((2, 2), np.float32))
    c = data.attach_copy(2, np.ones((2, 2), np.float32))
    c.version = 1
    assert data.hold_source(1) is c and data.peer_holds == 1
    assert data.claim_for_donation() is False      # held: functional
    data.release_source()
    assert data.claim_for_donation() is True and data.peer_holds == -1
    with pytest.raises(BeingOverwritten):
        data.hold_source(1)
    # the module that owns the array reads it whatever the claim
    assert data.hold_source(2) is c
    data.donation_committed()
    assert data.peer_holds == 0 and data.hold_source(1) is c
    data.release_source()
    # a host copy at the same version gives way to the one on a device
    h = data.attach_copy(0, np.ones((2, 2), np.float32))
    h.version = 1
    assert data.hold_source(1) is c
    data.release_source()
    h.version = 2
    assert data.hold_source(1) is h and data.peer_holds == 0


def test_the_donation_and_a_peers_landing_race_a_thousand_rounds():
    """Chip ``a`` holds a tile's newest version and gives it to a
    program that writes over it (the staging walk's ``_not_sole``, the
    donating call, the commit) while chip ``b`` lands the same tile: in
    every round either the landing held the array first and the donation
    was refused, or the claim came first and the landing was refused
    loudly or read the version after it.  Never a deleted array."""
    ctx, (a, b) = _two_chips()
    rounds = 1000
    step = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    plain = jax.jit(lambda x: x + 1.0)
    outcomes = {"landed": 0, "refused_peer": 0, "donated": 0, "functional": 0}
    errors = []
    try:
        data = data_create("x", payload=np.zeros((NB, NB), np.float32))
        _resident(a, data, 0.0, 1, dirty=True)
        go = threading.Barrier(2)

        def donor():
            try:
                for _ in range(rounds):
                    go.wait()
                    with a._res.lock:
                        arr = data.get_copy(a.data_index).payload
                        if data.claim_for_donation():
                            out = step(arr)
                            outcomes["donated"] += 1
                        else:
                            out = plain(arr)
                            outcomes["functional"] += 1
                        a._commit_output(data, out, out.nbytes, False)
            except BaseException as e:
                errors.append(e)
                go.abort()

        def lander():
            try:
                for _ in range(rounds):
                    go.wait()
                    try:
                        got = b._h2d.one(data)
                        float(got[0, 0])           # (a live array)
                        outcomes["landed"] += 1
                    except BeingOverwritten:
                        outcomes["refused_peer"] += 1
                    b._res.drop_stale([data])
                    with b._res.lock:               # land anew next round
                        b._res.forget(data)
                        b._res.drop(data, evicted=False)
            except BaseException as e:
                errors.append(e)
                go.abort()

        threads = [threading.Thread(target=f) for f in (donor, lander)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert outcomes["donated"] + outcomes["functional"] == rounds
        assert outcomes["landed"] + outcomes["refused_peer"] == rounds
        assert data.peer_holds == 0
        newest = data.newest_copy()
        assert newest.version == 1 + rounds
        assert float(np.asarray(newest.payload)[0, 0]) == float(rounds)
    finally:
        ctx.fini()


def test_not_sole_counts_a_peers_hold():
    """The staging walk of a chunk that would donate a tile finds a
    peer's landing holding its array: the task goes out functional,
    ``peer_holds_refused`` says why, and nothing stays claimed."""
    nt = 2
    held = []

    def hold(ctx, A):
        # a landing of A(0, 0)'s first version that never lets go
        # (nobody's yet: the hold alone is what the walk has to see)
        data = A.data_of(0, 0)
        with data.lock:
            data.peer_holds += 1
        held.append(data)

    got = _solve(nt, 4, before=hold)
    np.testing.assert_array_equal(got["L"], _solve(nt, 4)["L"])
    refused = sum(s["peer_holds_refused"] for s in got["stats"])
    assert refused >= 1
    assert sum(s["donation_refused"] for s in got["stats"]) == refused
    assert held[0].peer_holds == 1


# -- failure ----------------------------------------------------------------

def test_a_failing_program_on_one_module_fails_the_pool(monkeypatch):
    from parsec_tpu.ops import tiles

    def broken(A, B1, B2, **_):
        raise RuntimeError("planted")

    monkeypatch.setattr(tiles, "gemm_update_tpu", broken)
    S = _spd(4)
    A = _matrix(S)
    ctx = Context(nb_cores=2, accelerators=4)
    try:
        advise_data_on_devices(A, _accs(ctx), GRID, uplo="lower")
        tp = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=4, A=A)
        ctx.add_taskpool(tp)
        assert tp.wait(timeout=120) is False and tp.failed
        # the context stays usable: a healthy pool runs on all four
        monkeypatch.undo()
        B = _matrix(S)
        advise_data_on_devices(B, _accs(ctx), GRID, uplo="lower")
        tp2 = cholesky_ptg(use_tpu=True, use_cpu=False).taskpool(NT=4, A=B)
        ctx.add_taskpool(tp2)
        assert tp2.wait(timeout=120)
        ctx.flush()
        np.testing.assert_allclose(
            _lower(B), np.linalg.cholesky(S.astype(np.float64)),
            rtol=0, atol=2e-4)
    finally:
        ctx.fini()


def test_the_advice_takes_a_grid_of_as_many_accelerators():
    with Context(nb_cores=1, accelerators=2) as ctx:
        A = _matrix(_spd(3))
        with pytest.raises(ValueError, match="2 x 2"):
            advise_data_on_devices(A, _accs(ctx), (2, 2), uplo="lower")
        got = advise_data_on_devices(A, _accs(ctx), (1, 2), uplo="lower")
        assert got == {1: 4, 2: 2}
        assert A.data_of(2, 1).preferred_device == 2
        assert A.data_of(2, 2).preferred_device == 1
        # the upper triangle of a matrix stored full is left alone
        assert (0, 1) not in A.materialized_keys()


# -- the fence: one accelerator's way home is the parent's --------------------

#: ``HostWriter.commit`` calls of a 3 x 3 stencil of four sweeps, recorded
#: on the tree BEFORE ``accelerators`` existed (PR 51's parent, three runs
#: alike, pump and ``Context`` alike): the tile, the version landed, and
#: the home tile's layout (C-contiguous, writable, owning its memory)
_HOME_ORDER = [(0, 0), (0, 2), (1, 1), (0, 1), (2, 0), (1, 0), (2, 2),
               (1, 2), (2, 1)]
_HOME_COUNTERS = {"bytes_in": 9 * 32 * 32 * 4, "bytes_out": 9 * 32 * 32 * 4,
                  "scratch_bytes_out": 0, "wb_started_early": 9,
                  "wb_early_hits": 9, "wb_alias_fallbacks": 0,
                  "wb_zeros_landed": 0}


@pytest.mark.parametrize("route", ["pump", "context"])
def test_one_accelerators_way_home_is_what_it_was(route, monkeypatch):
    """With ``accelerators=1`` the bytes, the order and the layout of a
    stencil-shaped pool's tiles going home are the parent's."""
    from parsec_tpu.device import staging
    from parsec_tpu.ops.stencil import stencil_grid, stencil_taskpool

    grid = np.random.default_rng(7).standard_normal(
        (96, 96)).astype(np.float32)
    A = stencil_grid(grid, 3, 3)
    ids = {A.data_of(i, j).data_id: (i, j)
           for i in range(3) for j in range(3)}
    calls = []
    real = staging.HostWriter.commit

    def commit(self, data, version, host):
        landed = real(self, data, version, host)
        home = data.get_copy(0).payload
        calls.append((ids.get(data.data_id), version, host.nbytes, landed,
                      home.shape, home.flags.c_contiguous,
                      home.flags.writeable, home.flags.owndata))
        return landed

    monkeypatch.setattr(staging.HostWriter, "commit", commit)
    tp = stencil_taskpool(A, 4, use_tpu=True, use_cpu=False)
    if route == "pump":
        from parsec_tpu.dsl.native_exec import NativeExecutor

        ex = NativeExecutor(tp, native_device=True)
        dev, before = ex.device, dict(ex.device.stats)
        ex.run()
        ex.close()
    else:
        ctx = Context(nb_cores=2, accelerators=1)
        try:
            dev, = _accs(ctx)
            before = dict(dev.stats)
            ctx.add_taskpool(tp)
            assert tp.wait(timeout=120)
            dev.flush()
        finally:
            ctx.fini()
    assert calls == [(key, 1, 32 * 32 * 4, True, (32, 32), True, True, True)
                     for key in _HOME_ORDER]
    assert {k: dev.stats[k] - before.get(k, 0)
            for k in _HOME_COUNTERS} == _HOME_COUNTERS


def test_no_process_wide_allocator_call_in_the_runtime():
    """``mallopt`` / ``madvise`` / ``malloc_trim`` belong to a benchmark
    driver's own process (``benchmark/drivers/dtd.py``), never to
    ``parsec_tpu/``: where a tile lands in host memory is not tuned on
    the side."""
    import os
    import re

    root = os.path.dirname(os.path.abspath(devmod.__file__ + "/.."))
    found = []
    for where, _dirs, files in os.walk(root):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name)) as f:
                    if re.search(r"mallopt|madvise|malloc_trim", f.read()):
                        found.append(os.path.join(where, name))
    assert found == []
