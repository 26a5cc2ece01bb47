"""Document routing (parallel/routing.py): the reference's DJB hash and
floor-mod shard choice. The mesh lane that serves searches is pinned in
tests/test_mesh.py."""

from elasticsearch_tpu.parallel import djb_hash, shard_id


class TestRouting:
    def test_djb_matches_reference_semantics(self):
        # DJB2: h("") == 5381, h("a") == 5381*33 + 97
        assert djb_hash("") == 5381
        assert djb_hash("a") == 5381 * 33 + ord("a")

    def test_floor_mod_not_abs(self):
        # find an id with negative int32 hash: floor-mod keeps it in range
        neg = next(s for s in (f"doc-{i}-x" for i in range(10_000))
                   if djb_hash(s) < 0)
        assert 0 <= shard_id(neg, 5) < 5

    def test_routing_param_overrides_id(self):
        assert shard_id("whatever", 7, routing="user-1") == \
               shard_id("other", 7, routing="user-1")
