"""PageAllocator: refcounts, prefix cache, LRU eviction, KV events."""

import pytest

from dynamo_tpu.engine.page_table import KvEvent, PageAllocator


def test_basic_allocate_free():
    a = PageAllocator(num_pages=8, page_size=4)
    assert a.num_free == 7  # page 0 reserved
    pages = a.allocate(3)
    assert pages is not None and 0 not in pages
    assert a.num_free == 4
    a.free(pages)
    assert a.num_free == 7


def test_allocate_exhaustion_returns_none():
    a = PageAllocator(num_pages=4, page_size=4)
    assert a.allocate(3) is not None
    assert a.allocate(1) is None


def test_double_free_raises():
    a = PageAllocator(num_pages=4, page_size=4)
    (p,) = a.allocate(1)
    a.free([p])
    with pytest.raises(ValueError):
        a.free([p])


def test_prefix_cache_share_and_refcount():
    a = PageAllocator(num_pages=8, page_size=4)
    (p,) = a.allocate(1)
    a.register(p, seq_hash=111, parent_hash=None, tokens=(1, 2, 3, 4))
    # Second request hits the cache; page now has 2 refs.
    hit = a.lookup([111, 222])
    assert hit == [p]
    a.free([p])  # first owner leaves — still referenced
    assert a.lookup([111]) == [p]  # still cached + re-acquirable
    a.free([p])
    a.free([p])
    # rc 0 -> reclaimable but still matchable
    assert a.match_length([111]) == 1
    assert a.num_free == 7


def test_lru_eviction_emits_removed_event():
    events: list[KvEvent] = []
    a = PageAllocator(num_pages=4, page_size=4, on_event=events.append)
    pages = a.allocate(3)
    for i, p in enumerate(pages):
        a.register(p, seq_hash=100 + i, parent_hash=None, tokens=(i,) * 4)
    a.free(pages)  # all reclaimable, LRU order 100,101,102
    got = a.allocate(2)  # must evict 100 then 101
    assert got is not None
    removed = [e for e in events if e.kind == "removed"]
    assert [e.block_hashes[0] for e in removed] == [100, 101]
    assert a.match_length([102]) == 1
    assert a.match_length([100]) == 0


def test_stored_events_carry_chain_info():
    events: list[KvEvent] = []
    a = PageAllocator(num_pages=4, page_size=2, on_event=events.append)
    (p1,) = a.allocate(1)
    a.register(p1, seq_hash=7, parent_hash=None, tokens=(1, 2))
    (p2,) = a.allocate(1)
    a.register(p2, seq_hash=8, parent_hash=7, tokens=(3, 4))
    assert events[0].kind == "stored" and events[0].parent_hash is None
    assert events[1].parent_hash == 7
    assert events[1].token_blocks == ((3, 4),)


def test_clear_cache():
    a = PageAllocator(num_pages=6, page_size=4)
    pages = a.allocate(2)
    for i, p in enumerate(pages):
        a.register(p, seq_hash=50 + i, parent_hash=None, tokens=(i,) * 4)
    a.free(pages)
    n = a.clear_cache()
    assert n == 2
    assert a.match_length([50]) == 0
    assert a.num_free == 5


# -- native/python backend parity -------------------------------------------
# The pool bookkeeping runs in C++ (native/pool.cpp) when libdynamo_native is
# available; these drive the same random workload through both backends and
# assert identical page ids, capacity accounting, and KV events.


def _forced_python_allocator(monkeypatch, *args, **kwargs):
    from dynamo_tpu import native

    monkeypatch.setattr(native, "lib", lambda: None)
    a = PageAllocator(*args, **kwargs)
    assert a._np is None
    return a


def test_native_backend_active_when_lib_built():
    from dynamo_tpu.native import ensure_built

    if ensure_built() is None:
        pytest.skip("native library unavailable")
    a = PageAllocator(num_pages=8, page_size=4)
    assert a._np is not None


def test_native_python_parity_fuzz(monkeypatch):
    import random

    from dynamo_tpu.native import ensure_built

    if ensure_built() is None:
        pytest.skip("native library unavailable")

    ev_a, ev_b = [], []
    a = PageAllocator(num_pages=33, page_size=4, on_event=ev_a.append)
    assert a._np is not None
    b = _forced_python_allocator(
        monkeypatch, num_pages=33, page_size=4, on_event=ev_b.append
    )

    rng = random.Random(123)
    held_a, held_b = [], []  # parallel lists of page lists
    hashes = [rng.getrandbits(64) for _ in range(40)]
    next_hash = 0

    for step in range(2000):
        op = rng.random()
        assert a.num_free == b.num_free, f"step {step}"
        if op < 0.35:  # allocate
            n = rng.randrange(1, 5)
            ra, rb = a.allocate(n), b.allocate(n)
            assert ra == rb, f"step {step}: {ra} != {rb}"
            if ra is not None:
                held_a.append(ra)
                held_b.append(rb)
        elif op < 0.55 and held_a:  # free
            i = rng.randrange(len(held_a))
            a.free(held_a.pop(i))
            b.free(held_b.pop(i))
        elif op < 0.75 and held_a:  # register a held page under a chain hash
            i = rng.randrange(len(held_a))
            j = rng.randrange(len(held_a[i]))
            h = hashes[next_hash % len(hashes)] + next_hash
            next_hash += 1
            toks = tuple(rng.randrange(100) for _ in range(4))
            assert a.register(held_a[i][j], h, None, toks) == b.register(
                held_b[i][j], h, None, toks
            ), f"step {step}"
        elif op < 0.9:  # lookup a random chain
            k = rng.randrange(1, 6)
            chain = [hashes[rng.randrange(len(hashes))] for _ in range(k)]
            ra, rb = a.lookup(chain), b.lookup(chain)
            assert ra == rb, f"step {step}"
            if ra:
                held_a.append(ra)
                held_b.append(rb)
            assert a.match_length(chain) == b.match_length(chain)
        else:  # clear cache sometimes
            assert a.clear_cache() == b.clear_cache()

    assert a.stats == b.stats
    assert ev_a == ev_b
    # Drain everything and confirm full recovery in both.
    for pa, pb in zip(held_a, held_b):
        a.free(pa)
        b.free(pb)
    assert a.num_free == b.num_free
    assert a.clear_cache() == b.clear_cache()
    assert a.num_free == 32


@pytest.mark.parametrize("backend", ["native", "python"])
def test_register_says_whether_the_page_is_addressed(backend, monkeypatch):
    """What the engine's resumed walk (`Request.registered_blocks`) rests
    on: True = the page carries a registration (new, or from before: no
    second event), False = its content is cached under another page and
    the page may be offered again once that one is evicted."""
    events = []
    if backend == "python":
        a = _forced_python_allocator(
            monkeypatch, num_pages=4, page_size=2, on_event=events.append
        )
    else:
        from dynamo_tpu.native import ensure_built

        if ensure_built() is None:
            pytest.skip("native library unavailable")
        a = PageAllocator(num_pages=4, page_size=2, on_event=events.append)
    p1, p2 = a.allocate(2)
    assert a.register(p1, 7, None, (1, 2)) is True
    assert a.register(p1, 7, None, (1, 2)) is True
    assert a.register(p2, 7, None, (1, 2)) is False
    assert [e.kind for e in events] == ["stored"]
    a.free([p1])
    a.clear_cache()  # p1's registration goes
    assert a.register(p2, 7, None, (1, 2)) is True
    assert [e.kind for e in events] == ["stored", "removed", "stored"]
