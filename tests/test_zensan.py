"""zensan: the shadow-ledger sanitizer must (a) stay silent on every
legal flow, (b) catch each seeded corruption BY NAME, and (c) cost
nothing when disabled.

The seeded tests are the sanitizer's own CI gate: a refactor that
silently stops a hook from firing turns one of these red, not a
production incident."""

import pytest

from repro.analysis import zensan
from repro.analysis.zensan import ZensanViolation
from repro.core.history import HistoryStore
from repro.serving.kv_cache import PAGE_SIZE, PagePool, Request
from repro.serving.prefix_cache import PrefixCache
from repro.serving.tenancy import SharedPagePool


@pytest.fixture
def san():
    """Strict sanitizer for the test body; restores whatever was
    installed before (the REPRO_ZENSAN=1 CI instance, usually None)."""
    prev = zensan.SAN
    s = zensan.enable(strict=True)
    yield s
    zensan._install(prev)


@pytest.fixture
def lax():
    """Non-strict: accumulate violations for inspection."""
    prev = zensan.SAN
    s = zensan.enable(strict=False)
    yield s
    zensan._install(prev)


def _pod(pages=16, apps=("a", "b")):
    shared = SharedPagePool(pages, history=HistoryStore())
    views = {app: shared.view(app, policy="fixed", fixed_init_pages=1,
                              fixed_step_pages=1) for app in apps}
    return shared, views


def _req(rid, pages=1, max_new=4):
    toks = tuple(range(pages * PAGE_SIZE))
    return Request(rid, len(toks), max_new_tokens=max_new,
                   prompt_tokens=toks)


# -- clean flows stay silent --------------------------------------------------

def test_clean_two_tenant_flow(san):
    shared, views = _pod()
    reqs = {}
    for app, v in views.items():
        r = _req(f"{app}0", pages=2)
        assert v.try_admit(r)
        reqs[app] = r
        san.check(v)
    for _ in range(3):                      # grow + check each step
        for app, v in views.items():
            v.grow(reqs[app], horizon=1)
            san.check(v)
    # park/unpark round trip for one tenant
    va = views["a"]
    phys, phys_local = va.reclaim(reqs["a"])
    va.parked = True
    san.check(va)
    va.parked = False
    assert va.regrant(reqs["a"], len(phys), len(phys_local))
    san.unpark_done(va, "a")
    san.check(va)
    for app, v in views.items():
        v.release(reqs[app])
        san.check(v)
        v.close()
    assert san.violations == [] and san.events > 0


def test_clean_null_engine_serving(san):
    """End-to-end through Cluster/ServingEngine with the null executor:
    every step's quiescent check stays green."""
    from repro.runtime.cluster import Application, Cluster
    from repro.runtime.executors import NullExecutor
    from repro.runtime.options import ServeOptions

    cluster = Cluster(pods=1, history=HistoryStore(),
                      executor=NullExecutor(), pool_pages=16)
    h = cluster.submit(Application.serve(
        "tinyllama-1.1b", reduced=True, name="zs",
        serve=ServeOptions(max_batch=2)))
    for i in range(3):
        h.submit_request(Request(f"r{i}", PAGE_SIZE - 4, 6))
    for _ in range(200):
        if not h.step()["alive"]:
            break
    h.park()
    h.unpark()
    h.release()
    assert san.violations == []


def test_clean_private_pool_prefix_flow(san):
    pool = PagePool(8, app="solo")
    pool.prefix_cache = PrefixCache(("solo",), pool._give)
    r = _req("p0", pages=2)
    assert pool.try_admit(r)
    phys = pool.cache_donate(r.pages[:1])
    del r.pages[:1]
    r.shared_pages = phys
    created = pool.prefix_cache.insert(r.prompt_tokens[:PAGE_SIZE], 0, phys)
    r.prefix_nodes = created
    san.check(pool)
    pool.release(r)
    san.check(pool)
    pool.prefix_cache.flush()
    san.check(pool)
    assert san.violations == []


# -- seeded corruptions are caught BY NAME ------------------------------------

def test_seeded_double_free(san):
    shared, views = _pod()
    v = views["a"]
    r = _req("df0", pages=2)
    assert v.try_admit(r)
    phys = v.to_physical(r.pages)
    v.release(r)                            # legal free
    with pytest.raises(ZensanViolation, match=r"double-free"):
        shared._give(phys)                  # the bug: freed again


def test_seeded_refcount_leak(san):
    pool = PagePool(8, app="leak")
    pool.prefix_cache = PrefixCache(("leak",), pool._give)
    r = _req("rl0", pages=1)
    assert pool.try_admit(r)
    phys = pool.cache_donate(list(r.pages))
    r.pages = []
    node = pool.prefix_cache.insert(r.prompt_tokens, 0, phys)[0]
    node.refs += 1                          # the bug: a pin bypassing pin()
    with pytest.raises(ZensanViolation, match=r"refcount-leak"):
        san.check(pool)


def test_seeded_quota_overdraft(san, monkeypatch):
    from repro.serving import tenancy

    def buggy_alloc(self, n):
        """PoolView._alloc with its quota guard deleted."""
        got = self.shared._take(n)
        if got is None:
            return None
        self.used += n
        ids = self._new_ids(n)
        for vid, pid in zip(ids, got):
            self._remap[vid] = pid
        s = zensan.SAN
        if s is not None:
            s.grant(self, ids, got)
        return ids

    monkeypatch.setattr(tenancy.PoolView, "_alloc", buggy_alloc)
    shared = SharedPagePool(16, history=HistoryStore())
    v = shared.view("a", quota=2, policy="fixed", fixed_init_pages=1,
                    fixed_step_pages=1)
    with pytest.raises(ZensanViolation, match=r"quota-overdraft"):
        v.try_admit(_req("qo0", pages=3))


def test_seeded_stranded_park_receipt(san):
    shared, views = _pod()
    v = views["a"]
    r = _req("sp0", pages=2)
    assert v.try_admit(r)
    v.reclaim(r)                            # park receipt recorded
    with pytest.raises(ZensanViolation, match=r"stranded-park-receipt"):
        san.unpark_done(v, "a")             # ...but never regranted


def test_seeded_park_mismatch(san):
    shared, views = _pod()
    v = views["a"]
    r = _req("pm0", pages=2)
    assert v.try_admit(r)
    phys, _ = v.reclaim(r)
    with pytest.raises(ZensanViolation, match=r"park-mismatch"):
        v.regrant(r, len(phys) + 1)         # the bug: wrong page count


def test_seeded_id_escape(san):
    """A view-local id reaching a decode table (the runtime twin of
    zenlint ZL001) is flagged against the ledger."""
    shared, views = _pod()
    v = views["a"]
    r = _req("ie0", pages=1)
    assert v.try_admit(r)
    with pytest.raises(ZensanViolation, match=r"id-escape"):
        san.table(v, [list(r.pages)], [])   # untranslated view-local ids
    # the translated row is fine
    san.table(v, [v.to_physical(r.pages)], [])



def test_seeded_write_collision(san):
    """Two real decode lanes writing one block of one page (kv_write
    would keep only one lane's token) are flagged; the same block of
    another group, page or block is fine."""
    with pytest.raises(ZensanViolation, match=r"write-collision"):
        san.decode_writes([("a", "global", 3, 0), ("b", "global", 3, 0)])
    san.decode_writes([("a", "global", 3, 0), ("b", "global", 3, 1),
                       ("c", "global", 4, 0), ("d", "ring", 3, 0)])

def test_seeded_view_leak(san):
    shared, views = _pod()
    v = views["a"]
    assert v.try_admit(_req("vl0", pages=2))
    with pytest.raises(ZensanViolation, match=r"view-leak"):
        v.close()                           # the bug: close holding pages


def test_seeded_conservation_diff(lax):
    """A page silently dropped from a view's remap shows up in the
    check() sweep with the ledger-vs-real diff attached."""
    shared, views = _pod()
    v = views["a"]
    r = _req("cv0", pages=2)
    assert v.try_admit(r)
    lax.check(v)
    assert lax.violations == []
    v._remap.popitem()                      # the bug: lost a page
    lax.check(v)
    rules = {x.rule for x in lax.violations}
    assert "conservation" in rules
    assert any("ledger" in x.diff for x in lax.violations if x.diff)


def test_seeded_dense_slot(san):
    from types import SimpleNamespace
    runner = SimpleNamespace(slots={}, generated={})
    with pytest.raises(ZensanViolation, match=r"dense-slot"):
        san.dense_state(runner, [_req("ds0")])


# -- bounded schedule explorer ------------------------------------------------

def test_explorer_depth2_clean():
    prev = zensan.SAN
    res = zensan.explore(depth=2)
    assert zensan.SAN is prev               # save/restore held
    assert res.sequences == len(zensan.EXPLORE_OPS) ** 2
    assert res.ops_applied == res.sequences * 2
    assert res.ok, "\n".join(v.render() for v in res.violations[:10])


def test_explorer_depth3_clean():
    res = zensan.explore(depth=3)
    assert res.sequences == len(zensan.EXPLORE_OPS) ** 3
    assert res.ok, "\n".join(v.render() for v in res.violations[:10])


def test_explorer_catches_seeded_model_bug(monkeypatch):
    """Sanity: the explorer is not vacuously green -- a model whose
    preempt 'forgets' to uncharge quota trips the ledger."""
    real_dealloc = None
    from repro.serving import tenancy

    real_dealloc = tenancy.PoolView._dealloc

    def buggy_dealloc(self, pages):
        self.used += len(pages)             # the bug: double-charge
        return real_dealloc(self, pages)

    monkeypatch.setattr(tenancy.PoolView, "_dealloc", buggy_dealloc)
    res = zensan.explore(depth=2, ops=("grant_a", "preempt_a"))
    assert not res.ok
    assert any(v.rule == "conservation" for v in res.violations)


# -- disabled: zero footprint -------------------------------------------------

def test_disabled_leaves_no_shadow_state():
    prev = zensan.SAN
    zensan._install(None)
    try:
        shared, views = _pod()
        v = views["a"]
        r = _req("z0", pages=2)
        assert v.try_admit(r)
        v.release(r)
        assert not hasattr(shared, "_zs_ledger")
        assert not hasattr(v, "_zs_local")
    finally:
        zensan._install(prev)


def test_enable_mid_flight_adopts_live_state():
    """enable() after unobserved mutations must re-snapshot, not
    complain about history it never saw."""
    prev = zensan.SAN
    zensan._install(None)
    try:
        shared, views = _pod()
        v = views["a"]
        r = _req("mf0", pages=2)
        assert v.try_admit(r)               # unobserved
        s = zensan.enable(strict=True)
        san_r2 = _req("mf1", pages=1)
        assert v.try_admit(san_r2)
        s.check(v)
        v.release(r)
        v.release(san_r2)
        s.check(v)
        assert s.violations == []
    finally:
        zensan._install(prev)
