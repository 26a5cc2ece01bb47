"""ChaosRunner: seeded rounds of workload + disruption + parity sweep +
invariant checks, over a single-node twin-index ladder AND a live
multi-node cluster, with leak detectors armed throughout.

Every random choice flows from `ChaosOptions.seed`; the seed is
exported as `CHAOS_SEED` for the duration of the run so any assertion
raised anywhere underneath (including engine leak checks) carries the
reproducing integer in its message.
"""

from __future__ import annotations

import copy
import os
import random

from ...cluster.host_reduce import HOST_REDUCE_SETTING
from ...common.settings import Settings
from ...index.engine import SearcherLeakError
from . import detectors
from .oracle import ParityOracle, classify, control_plane_violations
from .scheme import DisruptionScheme
from .workload import SeededWorkload

# the twin-index ladder: same docs under every dense-lane configuration
# the engine documents as bitwise-equivalent (index-creation-time
# settings are the lane toggles)
_TWINS = [
    ("c-loop", {"index.search.stacked.enable": False,
                "index.search.blockwise.enable": False,
                "index.search.mesh.enable": False}),
    ("c-stacked", {"index.search.blockwise.enable": False,
                   "index.search.mesh.enable": False}),
    ("c-block", {"index.search.mesh.enable": False,
                 "index.search.block_docs": 64}),
    ("c-mesh", {}),
]

_KNN_SETTINGS = {"index.knn.ivf.nlist": 4, "index.knn.ivf.nprobe": 2,
                 "index.knn.ivf.min_docs": 16, "index.knn.precision": "f32"}


class ChaosFailure(AssertionError):
    """Any chaos-run failure: the message leads with the reproducing
    seed (the `REPRODUCE WITH` line of this harness)."""

    def __init__(self, seed: int, problems: list):
        detail = "\n  ".join(str(p) for p in problems)
        super().__init__(
            f"chaos run failed [CHAOS_SEED={seed}] — reproduce with "
            f"CHAOS_SEED={seed}:\n  {detail}")
        self.seed = seed
        self.problems = problems


class ChaosOptions:
    __test__ = False

    def __init__(self, seed: int, rounds: int = 3, docs_per_round: int = 48,
                 dims: int = 8, cluster_nodes: int = 3, shards: int = 4,
                 replicas: int = 1, transport: str = "local",
                 inject_parity_fault: bool = False,
                 raise_on_failure: bool = True,
                 extended_roster: bool = False, pods: int = 0):
        self.seed = seed
        self.rounds = rounds
        self.docs_per_round = docs_per_round
        self.dims = dims
        # 0 disables the cluster half (the cheap single-node-only mode)
        self.cluster_nodes = cluster_nodes
        self.shards = shards
        self.replicas = replicas
        self.transport = transport
        self.inject_parity_fault = inject_parity_fault
        self.raise_on_failure = raise_on_failure
        # opt-in kill/restart + clock-skew disruptions (scheme roster).
        # Off by default so pinned-seed schedules stay bit-identical.
        self.extended_roster = extended_roster
        # pod mode (ISSUE 19): every cluster node owns a disjoint device
        # slice and nodes spread over `pods` simulated hosts, so the
        # roster runs over the multi-host / per-node-pool transport
        self.pods = pods


class ChaosReport:
    __test__ = False

    def __init__(self, seed: int):
        self.seed = seed
        self.rounds = 0
        self.parity_checks = 0
        self.lane_checks = 0
        self.mismatches: list = []
        self.invariant_violations: list[str] = []
        self.disruptions: list[str] = []
        self.faults_injected = 0
        self.acked_writes = 0
        self.hedges_fired = 0

    def ok(self) -> bool:
        return not self.mismatches and not self.invariant_violations

    def as_dict(self) -> dict:
        return {"seed": self.seed, "rounds": self.rounds,
                "parity_checks": self.parity_checks,
                "lane_checks": self.lane_checks,
                "mismatches": len(self.mismatches),
                "invariant_violations": len(self.invariant_violations),
                "disruptions": list(self.disruptions),
                "faults_injected": self.faults_injected,
                "acked_writes": self.acked_writes}


class ChaosRunner:
    __test__ = False

    def __init__(self, path: str, options: ChaosOptions):
        self.path = str(path)
        self.opt = options
        self.rng = random.Random(options.seed)
        self.report = ChaosReport(options.seed)
        self.oracle = ParityOracle(options.inject_parity_fault)
        self.node = None
        self.cluster = None
        self.scheme = None
        self._acked: list[str] = []

    # -- lifecycle ----------------------------------------------------------

    def run(self) -> ChaosReport:
        prev_seed = os.environ.get("CHAOS_SEED")
        os.environ["CHAOS_SEED"] = str(self.opt.seed)
        detectors.arm()
        try:
            self._setup()
            for _ in range(self.opt.rounds):
                self._round()
                self.report.rounds += 1
            self._final_invariants()
        except Exception as e:
            # ANY unexpected failure must carry the reproducing seed
            raise ChaosFailure(self.opt.seed,
                               [f"{type(e).__name__}: {e}"]) from e
        finally:
            self._teardown()
            if prev_seed is None:
                os.environ.pop("CHAOS_SEED", None)
            else:
                os.environ["CHAOS_SEED"] = prev_seed
        self.report.parity_checks = self.oracle.checks
        self.report.lane_checks = self.oracle.lane_checks
        self.report.mismatches = list(self.oracle.mismatches)
        problems = self.report.mismatches + self.report.invariant_violations
        if problems and self.opt.raise_on_failure:
            raise ChaosFailure(self.opt.seed, problems)
        return self.report

    def _setup(self) -> None:
        from ...node import NodeService
        self.solo_work = SeededWorkload(
            random.Random(self.rng.randrange(2 ** 62)), self.opt.dims)
        self.node = NodeService(os.path.join(self.path, "solo"), Settings({
            # the chaos corpus is tiny; a latency-EWMA spike from first
            # compiles must not shed the parity sweep
            "node.search.qos.shed_latency_ms": 0}))
        mapping = self.solo_work.mapping()
        for name, extra in _TWINS:
            self.node.create_index(
                name, settings={"number_of_shards": 2,
                                **_KNN_SETTINGS, **extra},
                mappings={"_doc": mapping})
        if self.opt.cluster_nodes:
            from ...cluster.harness import TestCluster
            self.cluster_work = SeededWorkload(
                random.Random(self.rng.randrange(2 ** 62)), self.opt.dims)
            self.cluster = TestCluster(
                self.opt.cluster_nodes, os.path.join(self.path, "cluster"),
                transport=self.opt.transport, pods=self.opt.pods)
            client = self.cluster.client()
            client.create_index("docs", {
                "number_of_shards": self.opt.shards,
                "number_of_replicas": self.opt.replicas,
                **_KNN_SETTINGS})
            client.put_mapping("docs", "_doc", mapping)
            self.cluster.ensure_green()
            self.scheme = DisruptionScheme(
                self.cluster, random.Random(self.rng.randrange(2 ** 62)),
                extended_roster=self.opt.extended_roster)

    # -- one round ----------------------------------------------------------

    def _round(self) -> None:
        self._solo_writes()
        if self.cluster is not None:
            started = self.scheme.start_round()
            self.report.disruptions.extend(started)
            try:
                self._cluster_traffic_under_disruption()
            finally:
                self.scheme.heal()
        self._solo_parity_sweep()
        if self.cluster is not None:
            self._cluster_parity_sweep()
            if self.opt.pods:
                self._pod_invariants()
            self._acked_write_check()
            self.report.invariant_violations.extend(
                control_plane_violations(
                    [self.node, *self.cluster.nodes.values()]))
            self.report.faults_injected = self._cluster_faults()

    def _solo_writes(self) -> None:
        w = self.solo_work
        docs = w.next_docs(self.opt.docs_per_round)
        victims = w.victim_ids(self.rng.randint(2, 5))
        merge = self.rng.random() < 0.5
        # every twin sees the identical write/delete/merge sequence — the
        # precondition for cross-lane parity (stats included: a merge
        # purges deletes, so it must happen on ALL twins or none)
        for name, _ in _TWINS:
            for doc_id, src in docs:
                self.node.index_doc(name, doc_id, copy.deepcopy(src))
            for doc_id in victims:
                try:
                    self.node.delete_doc(name, doc_id)
                except Exception:
                    pass        # already deleted in an earlier round
            if merge:
                self.node.force_merge(name)
            self.node.refresh(name)

    def _search_lanes(self, index: str, body: dict):
        """Search with the lane-decision flight recorder armed (ISSUE
        16): returns (response, LaneRecorder) so the parity sweep can
        assert the replay actually rode the lane its label claims."""
        from ...common.device_stats import record_lanes
        with record_lanes() as rec:
            resp = self.node.search(index, copy.deepcopy(body))
        return resp, rec

    # lanes each twin may legitimately ride for the seeded text bodies:
    # the packed serve lane coalesces packed-servable plans even for solo
    # requests (on every twin), the sparse postings lane outranks the
    # dense ladder for pure-term shapes, and blockwise only engages when
    # the stack exceeds one block — so the claim is a set per twin, and
    # the check still catches the real failure (a twin silently riding
    # the LOOP lane because its configured dense lane declined)
    _TWIN_LANES = {
        "c-stacked": ("stacked", "stacked_blockwise", "sparse", "packed"),
        "c-block": ("stacked", "stacked_blockwise", "sparse", "packed"),
        "c-mesh": ("mesh", "sparse", "packed"),
    }

    def _solo_parity_sweep(self) -> None:
        texts = self.solo_work.text_queries(8)
        for body in texts:
            ref = self.node.search("c-loop", copy.deepcopy(body))
            for name, _ in _TWINS[1:]:
                got, rec = self._search_lanes(name, body)
                if self.oracle.compare(f"loop-vs-{name}", body, ref, got):
                    self.oracle.lane_check(f"loop-vs-{name}", rec,
                                           self._TWIN_LANES[name])
        # batched vs solo: the msearch lane coalesces compatible plans
        # into ONE Q>1 program; responses must equal the solo path's
        reqs = [({"index": "c-mesh"}, copy.deepcopy(b)) for b in texts[:4]]
        batch = self.node.msearch(reqs)
        for body, sub in zip(texts[:4], batch["responses"]):
            solo = self.node.search("c-mesh", copy.deepcopy(body))
            self.oracle.compare("batched-vs-solo", body, solo, sub)
        self._sorted_parity()
        self._subagg_parity()
        self._composite_parity()
        self._knn_parity()
        self._percolate_parity()
        self._script_parity()

    # sorted bodies ride the ISSUE 17 sorted device lanes (the sparse
    # postings lane never serves a sorted plan); the claim catches a
    # twin quietly answering sorted bodies through the per-segment loop
    _SORTED_TWIN_LANES = {
        "c-stacked": ("stacked", "stacked_blockwise", "packed"),
        "c-block": ("stacked", "stacked_blockwise", "packed"),
        "c-mesh": ("mesh", "packed"),
    }

    def _sorted_parity(self) -> None:
        """Sorted-query replay pairs (ISSUE 17): the encoded-key device
        sort on every dense twin vs the loop's materialized-value
        merge — documented bitwise — plus a search_after page-2 replay
        whose cursor is the reference page's last `sort`, so the
        duplicate-key (_shard, _doc) tie-break is part of the pair."""
        for body in self.solo_work.sorted_queries(4):
            ref = self.node.search("c-loop", copy.deepcopy(body))
            for name, _ in _TWINS[1:]:
                got, rec = self._search_lanes(name, body)
                if self.oracle.compare(f"sorted-loop-vs-{name}", body,
                                       ref, got):
                    self.oracle.lane_check(
                        f"sorted-loop-vs-{name}", rec,
                        self._SORTED_TWIN_LANES[name])
            hits = ref["hits"]["hits"]
            if not hits or "sort" not in hits[-1]:
                continue
            page2 = {**copy.deepcopy(body),
                     "search_after": copy.deepcopy(hits[-1]["sort"])}
            ref2 = self.node.search("c-loop", copy.deepcopy(page2))
            for name, _ in _TWINS[1:]:
                got, rec = self._search_lanes(name, page2)
                if self.oracle.compare(f"search-after-loop-vs-{name}",
                                       page2, ref2, got):
                    self.oracle.lane_check(
                        f"search-after-loop-vs-{name}", rec,
                        self._SORTED_TWIN_LANES[name])

    def _subagg_parity(self) -> None:
        """Sub-agg-tree replay pairs (ISSUE 17): the composite-bin
        device planner (histogram/terms parents, integer-exact leaf
        metrics) vs the host's recursive per-segment collect —
        documented bitwise on every twin."""
        for body in self.solo_work.subagg_queries(3):
            ref = self.node.search("c-loop", copy.deepcopy(body))
            for name, _ in _TWINS[1:]:
                got, rec = self._search_lanes(name, body)
                if self.oracle.compare(f"subagg-loop-vs-{name}", body,
                                       ref, got):
                    self.oracle.lane_check(f"subagg-loop-vs-{name}",
                                           rec, self._TWIN_LANES[name])

    def _composite_parity(self) -> None:
        """Composite + pipeline replay pairs (ISSUE 20): the composite
        collect and host-side pipeline render are lane-invariant by
        construction — every twin answers byte-equal to the loop, with
        an `after`-key page-2 replay so cursor pagination is part of
        the pair. On the mesh twin a composite body must decline the
        collective planner under its STABLE reason ("composite") — a
        renamed/dropped reason breaks the explain surface's contract."""
        for body in self.solo_work.composite_queries(3):
            ref = self.node.search("c-loop", copy.deepcopy(body))
            for name, _ in _TWINS[1:]:
                got, rec = self._search_lanes(name, body)
                self.oracle.compare(f"composite-loop-vs-{name}", body,
                                    ref, got)
                if name == "c-mesh" and "pages" in body["aggs"]:
                    want = ["composite"]
                    seen = sorted({e["reason"] for e in rec.entries
                                   if e["component"] == "coordinator.aggs"
                                   and e["lane"] == "mesh"
                                   and e["reason"] != "chosen"})
                    self.oracle.compare(
                        f"composite-decline-reason-{name}", body,
                        {"declines": want}, {"declines": seen})
            comp = (ref.get("aggregations") or {}).get("pages")
            if comp and comp.get("after_key"):
                page2 = copy.deepcopy(body)
                page2["aggs"]["pages"]["composite"]["after"] = \
                    copy.deepcopy(comp["after_key"])
                ref2 = self.node.search("c-loop", copy.deepcopy(page2))
                for name, _ in _TWINS[1:]:
                    got, _rec = self._search_lanes(name, page2)
                    self.oracle.compare(
                        f"composite-after-loop-vs-{name}", page2,
                        ref2, got)

    def _percolate_parity(self) -> None:
        """Reverse-search replay pairs (ISSUE 18): the dense doc×query
        matrix executor vs the per-doc loop reference over the SAME
        registry — documented bitwise, wildcard residuals merged through
        the loop rung on both sides. Queries register on EVERY twin (same
        writes on all twins is the cross-lane parity precondition — a
        one-twin registry would skew doc counts and idf), and re-register
        each round so the generation-keyed corpus cache turns over."""
        from ...common.device_stats import record_lanes
        from ...search import percolator as perc_mod

        queries = self.solo_work.percolator_queries(7)
        for name, _ in _TWINS:
            for qi, q in enumerate(queries):
                self.node.index_doc(name, f"pq-{qi}", {"query": q},
                                    type_name=".percolator")
            self.node.refresh(name)
        name = _TWINS[1][0]
        svc = self.node.indices[name]
        for doc in self.solo_work.percolate_docs(4):
            registry = perc_mod.parsed_registry(svc)
            _, seg, root = perc_mod.build_doc_segment(
                svc, copy.deepcopy(doc))
            ref_ids = sorted(perc_mod.loop_match(registry, seg, root))
            ref = {"total": len(ref_ids),
                   "matches": [{"_index": name, "_id": i}
                               for i in ref_ids]}
            with record_lanes() as rec:
                got = self.node.percolate(name, {"doc": copy.deepcopy(doc)})
            got_c = {"total": got["total"], "matches": got["matches"]}
            if self.oracle.compare("percolate-dense-vs-loop",
                                   {"doc": doc}, ref, got_c):
                self.oracle.lane_check("percolate-dense-vs-loop", rec,
                                       ("dense", "mesh"))

    def _script_parity(self) -> None:
        """Compiled script_score vs the host evaluator (ISSUE 18): the
        SAME expression, once compiled to the fused device op and once
        wrapped in a host-only no-op conditional (`(e) if true else 0.0`
        — an IfExp the compiler declines with a stable reason) so it
        rides the per-doc host evaluator. Both lanes evaluate in f64 and
        the expression pool sticks to the exact-IEEE subset, so scores
        must match bitwise."""
        for w, expr, params in self.solo_work.script_exprs(3):
            def body(src):
                return {"size": 10, "query": {"function_score": {
                    "query": {"match": {"body": w}},
                    "script_score": {"script": src,
                                     "params": dict(params)},
                    "boost_mode": "replace"}}}
            ref, _ref_rec = self._search_lanes(
                "c-stacked", body(f"({expr}) if true else 0.0"))
            got, rec = self._search_lanes("c-stacked", body(expr))
            if self.oracle.compare("script-compiled-vs-host",
                                   body(expr), ref, got):
                self.oracle.lane_check("script-compiled-vs-host", rec,
                                       "compiled")

    def _knn_parity(self) -> None:
        for body in self.solo_work.knn_queries(3):
            knn = body["knn"]
            exact = {**body, "knn": {**knn, "exact": True}}
            ref, ref_rec = self._search_lanes("c-loop", exact)
            self.oracle.lane_check("knn-exact-ref", ref_rec, "exact")
            # IVF with nprobe >= nlist routes to the exact kernel —
            # documented bitwise parity, same index
            full = {**body, "knn": {**knn, "nprobe": 64}}
            got, rec = self._search_lanes("c-loop", full)
            self.oracle.compare("ivf-full-vs-exact", body, ref, got)
            self.oracle.lane_check("ivf-full-vs-exact", rec, "exact")
            # the exact kernel across twins (mesh exact lane declines to
            # the fan-out; either way the result is the same program)
            got, rec = self._search_lanes("c-mesh", exact)
            self.oracle.compare("knn-exact-loop-vs-mesh", body, ref, got)
            self.oracle.lane_check("knn-exact-loop-vs-mesh", rec, "exact")
            # int8 through the mesh lane vs the per-shard fan-out — the
            # documented quantized bitwise pair (f32-vs-quantized is
            # approximate by design and is NOT compared). The lane claim
            # is conditional: whenever the fan-out side built the
            # quantized tier, the mesh side must have rode mesh_knn —
            # both sides quietly falling back to the same rung would
            # pass parity without testing the pair at all
            int8 = {**body, "knn": {**knn, "quantization": "int8"}}
            ref8, ref8_rec = self._search_lanes("c-loop", int8)
            got8, got8_rec = self._search_lanes("c-mesh", int8)
            self.oracle.compare("knn-int8-loop-vs-mesh", body, ref8, got8)
            if ref8_rec.chose("ann_quantized"):
                self.oracle.lane_check("knn-int8-loop-vs-mesh", got8_rec,
                                       "mesh_knn")
        fbody = self.solo_work.filtered_knn_query()
        fref, fref_rec = self._search_lanes("c-loop", fbody)
        fgot, fgot_rec = self._search_lanes("c-mesh", fbody)
        self.oracle.compare("knn-filtered-loop-vs-mesh", fbody, fref, fgot)
        if fref_rec.chose("ann"):
            self.oracle.lane_check("knn-filtered-loop-vs-mesh", fgot_rec,
                                   "mesh_knn")

    # -- cluster half -------------------------------------------------------

    def _client(self):
        return self.cluster.client()

    def _cluster_traffic_under_disruption(self) -> None:
        w = self.cluster_work
        client = self._client()
        # fault detection runs WITH the faults live — the master must
        # react (remove the isolated node / step down), never crash
        self.cluster.detect_once()
        for doc_id, src in w.next_docs(self.opt.docs_per_round // 2):
            try:
                client.index_doc("docs", doc_id, src)
                self._acked.append(doc_id)
                self.report.acked_writes += 1
            except Exception as e:
                v = classify(e, disrupted=True)
                if v:
                    self.report.invariant_violations.append(f"write: {v}")
        for body in w.text_queries(4):
            try:
                client.search("docs", body)
            except Exception as e:
                v = classify(e, disrupted=True)
                if v:
                    self.report.invariant_violations.append(f"search: {v}")
        for doc_id in w.victim_ids(2):
            try:
                client.get_doc("docs", doc_id)
            except Exception as e:
                v = classify(e, disrupted=True)
                if v:
                    self.report.invariant_violations.append(f"get: {v}")
        self.cluster.detect_once()

    def _cluster_parity_sweep(self) -> None:
        """Post-heal: host-reduce vs the per-shard transport merge on
        the SAME queries (the cluster's lane pair), toggled live via the
        cluster setting."""
        client = self._client()
        # recoveries stream on background threads: wait for every copy
        # to be STARTED, and for the rebalancer's moves to hand over,
        # before refreshing, or a copy can come up BETWEEN the two
        # compared searches serving a pre-refresh view
        self.cluster.ensure_settled(20.0)
        client.refresh("docs")
        bodies = self.cluster_work.text_queries(4)
        bodies.append({"size": 5, "knn": {
            "field": "vec", "query_vector": self.cluster_work.vector(),
            "k": 5}})
        from ...common.device_stats import record_lanes
        for body in bodies:
            try:
                with record_lanes() as got_rec:
                    got = client.search("docs", copy.deepcopy(body))
                self._set_cluster_setting(
                    "cluster.search.host_reduce.enable", False)
                with record_lanes() as want_rec:
                    want = client.search("docs", copy.deepcopy(body))
                self.oracle.compare("host-reduce-vs-fanout", body, want, got)
                # lane claims (ISSUE 16): with the setting ON the
                # coordinator must at least CONSULT the host-reduce
                # ladder (a chosen lane or an explained decline —
                # contextvars ride the per-host fan-out threads); with it
                # OFF, riding host_reduce anyway means the toggle is dead
                if not any(e["lane"] == "host_reduce"
                           for e in got_rec.entries):
                    self.report.invariant_violations.append(
                        f"host-reduce ladder never consulted with "
                        f"{HOST_REDUCE_SETTING}=true for {body!r}")
                if want_rec.chose("host_reduce"):
                    self.report.invariant_violations.append(
                        f"host_reduce lane rode with "
                        f"{HOST_REDUCE_SETTING}=false for {body!r}")
            finally:
                self._set_cluster_setting(
                    "cluster.search.host_reduce.enable", True)

    def _pod_invariants(self) -> None:
        """Pod-mode invariants (ISSUE 19): every surviving node OWNS a
        disjoint device slice; on each node co-hosting >= 2 shards the
        host reduce rides that node's OWN mesh (a direct, deterministic
        per-node probe — the sweep's coordinator-side copy choice is
        adaptive); and the per-node data plane never touches the shared
        EXEC_LOCK."""
        from ...cluster.host_reduce import try_host_reduce
        from ...parallel.mesh_exec import exec_lock_stats
        viol = self.report.invariant_violations
        live = [n for n in self.cluster.nodes.values() if not n.closed]
        owner: dict[int, str] = {}
        for n in live:
            pool = getattr(n, "device_pool", None)
            if pool is None:
                viol.append(f"pod mode: {n.node_id} owns no device pool")
                continue
            for did in pool.devkey:
                if did in owner:
                    viol.append(f"pod mode: device {did} owned by both "
                                f"{owner[did]} and {n.node_id}")
                owner[did] = n.node_id
        shared0 = exec_lock_stats()["shared_acquisitions"]
        rode = 0
        for n in live:
            if getattr(n, "device_pool", None) is None:
                continue
            with n._shards_lock:
                sids = sorted(sid for (ix, sid), h in n._shards.items()
                              if ix == "docs" and h.engine is not None)
            if len(sids) < 2:
                continue
            # cap the group at what the node's slice can mesh (s_pad
            # must fit the pool) — the ride itself is what's asserted
            cap = len(n.device_pool.devices)
            out, reason = try_host_reduce(
                n, "docs", sids[:cap], {"query": {"match_all": {}}},
                10, None)
            if out is None:
                viol.append(f"pod mode: host reduce declined on "
                            f"{n.node_id} ({reason})")
            else:
                rode += 1
            self.oracle.lane_checks += 1
        if live and not rode:
            viol.append("pod mode: host reduce rode no node's mesh")
        shared1 = exec_lock_stats()["shared_acquisitions"]
        if shared1 != shared0:
            viol.append(
                f"pod mode: per-node reduce took the shared EXEC_LOCK "
                f"{shared1 - shared0}x — pools must dispatch lock-free")

    def _set_cluster_setting(self, key: str, val) -> None:
        master = self.cluster.master_node()

        def task(cur):
            st = cur.mutate()
            st.data.setdefault("settings", {})[key] = val
            return st
        master.cluster.submit_task(f"chaos-setting[{key}]", task)

    def _acked_write_check(self) -> None:
        """Every write acked on the quorum side must be retrievable
        after the partition heals (the split-brain acked-write
        invariant)."""
        client = self._client()
        sample = self._acked if len(self._acked) <= 20 \
            else self.rng.sample(self._acked, 20)
        for doc_id in sample:
            try:
                got = client.get_doc("docs", doc_id)
                found = bool(got.get("found"))
            except Exception as e:
                self.report.invariant_violations.append(
                    f"acked write [{doc_id}] unreadable after heal: {e!r}")
                continue
            if not found:
                self.report.invariant_violations.append(
                    f"acked write [{doc_id}] lost after heal")

    def _cluster_faults(self) -> int:
        fs = getattr(self.cluster.network, "fault_stats", None)
        return fs()["faults_injected_total"] if fs else 0

    # -- teardown invariants ------------------------------------------------

    def _final_invariants(self) -> None:
        if self.cluster is not None:
            hedged = sum(n.hedge_stats.get("fired", 0)
                         for n in self.cluster.nodes.values())
            self.report.hedges_fired = hedged

    def _teardown(self) -> None:
        viol = self.report.invariant_violations
        if self.cluster is not None:
            for n in self.cluster.nodes.values():
                try:
                    if not n.closed:
                        n.close()
                except SearcherLeakError as e:
                    viol.append(str(e))
            if hasattr(self.cluster.network, "close"):
                self.cluster.network.close()
            self.cluster = None
        if self.node is not None:
            caches, breakers = self.node.caches, self.node.breakers
            try:
                self.node.close()
            except SearcherLeakError as e:
                viol.append(str(e))
            # after close every cache owner is gone: residue in any tier
            # (or any non-drained breaker) is a real leak
            viol.extend(detectors.cache_problems(caches))
            viol.extend(detectors.breaker_problems(breakers))
            self.node = None
