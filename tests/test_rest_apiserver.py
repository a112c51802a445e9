"""RestClient against a real HTTP apiserver.

Every other control-plane test talks to FakeCluster in-process; here the
same store is served over HTTP (control/k8s/apiserver.py) and driven
through RestClient — the client-go analogue controllers use on a live
cluster. Covers the claims rest.py makes: CRUD verbs, status subresource,
merge/json patch, label/field selectors, 404/409 mapping, chunked watch
streams, and a controller running identically on both backends.
"""

import threading
import time

import pytest

from kubeflow_tpu.control.jaxjob import types as JT
from kubeflow_tpu.control.jaxjob.controller import build_controller, worker_name
from kubeflow_tpu.control.k8s import objects as ob
from kubeflow_tpu.control.k8s.apiserver import ApiServer, client_for, parse_api_path
from kubeflow_tpu.control.k8s.fake import FakeCluster
from kubeflow_tpu.control.runtime import seed_controller


@pytest.fixture()
def server():
    s = ApiServer().serve_background()
    yield s
    s.shutdown()


@pytest.fixture()
def client(server):
    return client_for(server)


def wait_for(fn, timeout=10.0, period=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(period)
    raise TimeoutError("condition not met")


class TestPathParsing:
    def test_core_namespaced(self):
        p = parse_api_path("/api/v1/namespaces/ns1/pods/p1")
        assert (p.api_version, p.kind, p.namespace, p.name) == \
            ("v1", "Pod", "ns1", "p1")

    def test_group_crd_with_status(self):
        p = parse_api_path(
            "/apis/kubeflow.org/v1/namespaces/ns1/jaxjobs/j/status")
        assert p.api_version == "kubeflow.org/v1"
        assert (p.kind, p.name, p.subresource) == ("JAXJob", "j", "status")

    def test_cluster_scoped(self):
        p = parse_api_path("/apis/kubeflow.org/v1/profiles/team-a")
        assert (p.kind, p.namespace, p.name) == ("Profile", None, "team-a")

    def test_unknown_plural_rejected(self):
        with pytest.raises(LookupError):
            parse_api_path("/api/v1/frobnicators")


class TestCrudOverHttp:
    def test_create_get_roundtrip(self, client):
        cm = ob.new_object("v1", "ConfigMap", "cm", "default")
        cm["data"] = {"k": "v"}
        client.create(cm)
        got = client.get("v1", "ConfigMap", "cm", "default")
        assert got["data"] == {"k": "v"}
        assert ob.meta(got)["resourceVersion"]

    def test_get_missing_raises_notfound(self, client):
        with pytest.raises(ob.NotFound):
            client.get("v1", "ConfigMap", "nope", "default")
        assert client.get_or_none("v1", "ConfigMap", "nope", "default") is None

    def test_create_duplicate_raises_conflict(self, client):
        obj = ob.new_object("v1", "ConfigMap", "cm", "default")
        client.create(obj)
        with pytest.raises(ob.Conflict):
            client.create(obj)

    def test_update_and_stale_rv_conflict(self, client):
        """The optimistic-concurrency 409 path controllers rely on."""
        cm = ob.new_object("v1", "ConfigMap", "cm", "default")
        cm["data"] = {"v": "1"}
        client.create(cm)
        fresh = client.get("v1", "ConfigMap", "cm", "default")
        stale = ob.deep_copy(fresh)
        fresh["data"]["v"] = "2"
        client.update(fresh)
        stale["data"]["v"] = "3"
        with pytest.raises(ob.Conflict):
            client.update(stale)

    def test_status_subresource_does_not_touch_spec(self, client):
        client.create(JT.new_jaxjob("j1", replicas=1))
        job = client.get(JT.API_VERSION, JT.KIND, "j1", "default")
        job["status"] = {"conditions": [{"type": "Created", "status": "True"}]}
        job["spec"]["replicas"] = 99  # must be ignored by /status
        client.update_status(job)
        got = client.get(JT.API_VERSION, JT.KIND, "j1", "default")
        assert got["status"]["conditions"][0]["type"] == "Created"
        assert got["spec"]["replicas"] == 1

    def test_merge_and_json_patch(self, client):
        cm = ob.new_object("v1", "ConfigMap", "cm", "default")
        cm["data"] = {"a": "1"}
        client.create(cm)
        client.patch("v1", "ConfigMap", "cm", {"data": {"b": "2"}}, "default")
        got = client.get("v1", "ConfigMap", "cm", "default")
        assert got["data"] == {"a": "1", "b": "2"}
        client.patch("v1", "ConfigMap", "cm",
                     [{"op": "remove", "path": "/data/a"}], "default")
        got = client.get("v1", "ConfigMap", "cm", "default")
        assert got["data"] == {"b": "2"}

    def test_delete(self, client):
        client.create(ob.new_object("v1", "ConfigMap", "cm", "default"))
        client.delete("v1", "ConfigMap", "cm", "default")
        assert client.get_or_none("v1", "ConfigMap", "cm", "default") is None

    def test_list_with_selectors(self, client):
        for i, role in enumerate(["web", "web", "db"]):
            client.create(ob.new_object("v1", "Pod", f"p{i}", "default",
                                        labels={"role": role}))
        assert len(client.list("v1", "Pod", "default")) == 3
        web = client.list("v1", "Pod", "default",
                          label_selector={"matchLabels": {"role": "web"}})
        assert {ob.meta(p)["name"] for p in web} == {"p0", "p1"}
        by_name = client.list("v1", "Pod", "default",
                              field_selector={"metadata.name": "p2"})
        assert len(by_name) == 1
        # list items get apiVersion/kind backfilled (apiserver omits them)
        assert by_name[0]["kind"] == "Pod"

    def test_cluster_scoped_objects(self, client):
        client.create(ob.new_object("v1", "Namespace", "team-x"))
        assert client.get("v1", "Namespace", "team-x")["kind"] == "Namespace"


class TestWatchOverHttp:
    def test_watch_streams_added_and_modified(self, client, server):
        stream = client.watch("v1", "ConfigMap", "default")
        events = []
        got_two = threading.Event()

        def consume():
            for ev in stream:
                events.append(ev)
                if len(events) >= 2:
                    got_two.set()
                    return

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        time.sleep(0.3)  # let the watch connect
        cm = ob.new_object("v1", "ConfigMap", "cm", "default")
        cm["data"] = {"v": "1"}
        client.create(cm)
        obj = client.get("v1", "ConfigMap", "cm", "default")
        obj["data"]["v"] = "2"
        client.update(obj)
        assert got_two.wait(10.0), f"saw only {events}"
        stream.stop()
        assert [e.type for e in events[:2]] == ["ADDED", "MODIFIED"]
        assert events[1].object["data"]["v"] == "2"


class TestControllerOverHttp:
    def test_jaxjob_gang_identical_on_both_backends(self, server, client):
        """The 'done' bar: one controller test passing identically on
        FakeCluster and RestClient backends."""
        # -- HTTP backend: production run() mode (threads + watch streams)
        ctl = build_controller(client)
        ctl.run(workers=1)
        try:
            client.create(JT.new_jaxjob("train", replicas=2,
                                        accelerator="tpu-v5-lite-podslice",
                                        topology="2x4"))
            pods = wait_for(
                lambda: (lambda ps: ps if len(ps) == 2 else None)(
                    client.list("v1", "Pod", "default")))
        finally:
            ctl.stop()
        http_names = {ob.meta(p)["name"] for p in pods}

        # -- in-process FakeCluster backend: hermetic drain mode
        fake = FakeCluster()
        fctl = seed_controller(build_controller(fake))
        fake.create(JT.new_jaxjob("train", replicas=2,
                                  accelerator="tpu-v5-lite-podslice",
                                  topology="2x4"))
        for _ in range(6):
            fctl.run_until_idle(advance_delayed=True)
        fake_names = {ob.meta(p)["name"]
                      for p in fake.list("v1", "Pod", namespace="default")}

        assert http_names == fake_names == {worker_name("train", i)
                                            for i in range(2)}
        # env contract survives the HTTP round trip
        pod = client.get("v1", "Pod", worker_name("train", 1), "default")
        env = {e["name"]: e["value"]
               for e in pod["spec"]["containers"][0]["env"]}
        assert env[JT.ENV_NPROC] == "2"


class TestLeaderElectionOverHttp:
    def test_two_electors_through_rest_client(self, server):
        """Leader election over the real HTTP wire: JSON-serialized
        MicroTime strings, 409 arbitration between two RestClients."""
        from kubeflow_tpu.control.k8s.rest import RestClient
        from kubeflow_tpu.control.leases import LeaderElector

        t = {"now": 5000.0}
        a = LeaderElector(RestClient(base_url=server.url),
                          "nb-controller", identity="pod-a",
                          clock=lambda: t["now"])
        b = LeaderElector(RestClient(base_url=server.url),
                          "nb-controller", identity="pod-b",
                          clock=lambda: t["now"])
        assert a.try_acquire() is True
        assert b.try_acquire() is False
        t["now"] += 16  # expiry -> takeover over HTTP
        assert b.try_acquire() is True
        assert a.try_acquire() is False
        b.release()
        assert a.try_acquire() is True


class TestWatchConformance:
    """The corners real kube-apiservers exercise that the round-2 review
    flagged: resume-after-disconnect, bookmarks, 410 Gone -> relist, paginated
    lists under concurrent writes, stale-patch 409."""

    def _consume(self, stream, events, stop_at):
        done = threading.Event()

        def run():
            for ev in stream:
                events.append(ev)
                if len(events) >= stop_at:
                    done.set()
                    return

        threading.Thread(target=run, daemon=True).start()
        return done

    def test_watch_resumes_after_dropped_connection(self, client, server):
        """Events created while the client is between connections MUST be
        delivered after reconnect (resume from last resourceVersion)."""
        stream = client.watch("v1", "ConfigMap", "default")
        events: list = []
        done = self._consume(stream, events, stop_at=3)
        time.sleep(0.3)
        cm = ob.new_object("v1", "ConfigMap", "a", "default")
        client.create(cm)
        for _ in range(100):  # the first event pins the client's rv
            if events:
                break
            time.sleep(0.05)
        assert events, "watch never delivered the first event"
        server.drop_watches()  # mid-stream disconnect
        # these happen while the client has no connection
        client.create(ob.new_object("v1", "ConfigMap", "b", "default"))
        client.create(ob.new_object("v1", "ConfigMap", "c", "default"))
        assert done.wait(10.0), f"saw only {[e.object['metadata']['name'] for e in events]}"
        stream.stop()
        names = [e.object["metadata"]["name"] for e in events[:3]]
        assert names == ["a", "b", "c"]  # nothing lost, nothing duplicated

    def test_bookmarks_advance_resume_point_past_other_kinds(self):
        """An idle ConfigMap watch must not rewind behind churn on other
        kinds: BOOKMARKs advance rv past the churn, so after a drop the
        resume succeeds directly. The tiny history window makes the
        no-bookmark fallback observable: without bookmarks the resume
        would 410 -> relist and re-yield 'seen' as a duplicate MODIFIED —
        the assertion below fails in that world."""
        cluster = FakeCluster(history_limit=6)
        srv = ApiServer(cluster).serve_background()
        srv.bookmark_interval = 0.2
        try:
            c = client_for(srv)
            stream = c.watch("v1", "ConfigMap", "default")
            events: list = []
            done = self._consume(stream, events, stop_at=2)
            time.sleep(0.3)
            c.create(ob.new_object("v1", "ConfigMap", "seen", "default"))
            # churn another kind PAST the history window, then idle long
            # enough for a bookmark carrying the post-churn rv
            for i in range(8):
                c.create(ob.new_object("v1", "Secret", f"s{i}", "default"))
            time.sleep(0.8)
            srv.drop_watches()
            c.create(ob.new_object("v1", "ConfigMap", "after", "default"))
            assert done.wait(10.0)
            stream.stop()
            assert [(e.type, e.object["metadata"]["name"])
                    for e in events[:2]] == \
                [("ADDED", "seen"), ("ADDED", "after")]
        finally:
            srv.shutdown()

    def test_too_old_rv_gets_410_then_relist(self, server):
        """History window exhausted: the watch must 410 and the client
        must relist (each live object re-yielded) and keep going."""
        cluster = FakeCluster(history_limit=4)
        srv = ApiServer(cluster).serve_background()
        try:
            c = client_for(srv)
            stream = c.watch("v1", "ConfigMap", "default")
            events: list = []

            def consume_forever():
                for ev in stream:
                    events.append(ev)

            threading.Thread(target=consume_forever, daemon=True).start()
            time.sleep(0.3)
            c.create(ob.new_object("v1", "ConfigMap", "first", "default"))
            for _ in range(100):
                if events:
                    break
                time.sleep(0.05)
            assert events, "watch never delivered the first event"
            srv.drop_watches()
            # blow past the 4-event history while disconnected
            for i in range(8):
                c.create(ob.new_object("v1", "Secret", f"x{i}", "default"))
            c.create(ob.new_object("v1", "ConfigMap", "second", "default"))
            # reconnect -> 410 -> relist: both live ConfigMaps re-yielded
            seen = threading.Event()

            def wait_for_second():
                while not any(
                        e.object["metadata"]["name"] == "second"
                        for e in events):
                    time.sleep(0.05)
                seen.set()

            threading.Thread(target=wait_for_second, daemon=True).start()
            assert seen.wait(10.0), \
                f"relist never surfaced: {[e.object['metadata']['name'] for e in events]}"
            stream.stop()
            names = {e.object["metadata"]["name"] for e in events}
            assert {"first", "second"} <= names
        finally:
            srv.shutdown()

    def test_relist_synthesizes_deleted_for_gap_deletions(self):
        """An object the stream had seen that vanishes during a 410 gap
        must surface as a DELETED event after the relist (informers diff
        the relist against their store the same way)."""
        cluster = FakeCluster(history_limit=4)
        srv = ApiServer(cluster).serve_background()
        try:
            c = client_for(srv)
            stream = c.watch("v1", "ConfigMap", "default")
            events: list = []

            def consume_forever():
                for ev in stream:
                    events.append(ev)

            threading.Thread(target=consume_forever, daemon=True).start()
            time.sleep(0.3)
            doomed = ob.new_object("v1", "ConfigMap", "doomed", "default",
                                   labels={"owner-label": "gang-a"})
            c.create(doomed)
            c.create(ob.new_object("v1", "ConfigMap", "keeper", "default"))
            for _ in range(100):
                if len(events) >= 2:
                    break
                time.sleep(0.05)
            assert len(events) >= 2
            srv.drop_watches()
            c.delete("v1", "ConfigMap", "doomed", "default")
            for i in range(8):  # truncate history past the deletion
                c.create(ob.new_object("v1", "Secret", f"z{i}", "default"))
            deleted_seen = threading.Event()

            def wait_deleted():
                while not any(e.type == "DELETED" and
                              e.object["metadata"]["name"] == "doomed"
                              for e in events):
                    time.sleep(0.05)
                deleted_seen.set()

            threading.Thread(target=wait_deleted, daemon=True).start()
            assert deleted_seen.wait(10.0), \
                f"no DELETED for doomed in {[(e.type, e.object['metadata']['name']) for e in events]}"
            stream.stop()
            # the survivor resyncs as MODIFIED, not DELETED
            assert not any(e.type == "DELETED" and
                           e.object["metadata"]["name"] == "keeper"
                           for e in events)
            # informer semantics: the synthesized DELETED carries the
            # LAST-KNOWN full object (labels/ownerRefs) so secondary
            # mappers still resolve the owning CR
            deleted = next(e for e in events if e.type == "DELETED" and
                           e.object["metadata"]["name"] == "doomed")
            assert deleted.object["metadata"].get("labels", {}).get(
                "owner-label") == "gang-a"
        finally:
            srv.shutdown()


class TestListPagination:
    def test_client_follows_continue_tokens(self, client, server):
        for i in range(7):
            client.create(ob.new_object("v1", "ConfigMap", f"cm{i}", "default"))
        client.list_chunk = 3  # force 3 pages
        items = client.list("v1", "ConfigMap", "default")
        assert [ob.meta(o)["name"] for o in items] == [f"cm{i}" for i in range(7)]
        assert all(o.get("kind") == "ConfigMap" for o in items)

    def test_pages_are_snapshot_consistent_under_writes(self, server):
        """Objects created/deleted between page fetches must not corrupt
        the pagination: later pages come from the original snapshot."""
        cluster = server.cluster
        for i in range(6):
            cluster.create(ob.new_object("v1", "ConfigMap", f"p{i}", "default"))
        page1, cont, rv = cluster.list_page("v1", "ConfigMap", "default",
                                            limit=3)
        assert [ob.meta(o)["name"] for o in page1] == ["p0", "p1", "p2"]
        # concurrent writes between pages
        cluster.create(ob.new_object("v1", "ConfigMap", "p2a", "default"))
        cluster.delete("v1", "ConfigMap", "p4", "default")
        page2, cont2, _ = cluster.list_page("v1", "ConfigMap", "default",
                                            limit=3, continue_token=cont)
        assert cont2 == ""
        # the snapshot still shows p4 and not p2a — page1+page2 is exactly
        # the collection as of the first request
        assert [ob.meta(o)["name"] for o in page2] == ["p3", "p4", "p5"]

    def test_expired_continue_token_is_410(self, server):
        cluster = server.cluster
        for i in range(4):
            cluster.create(ob.new_object("v1", "ConfigMap", f"q{i}", "default"))
        _, cont, _ = cluster.list_page("v1", "ConfigMap", "default", limit=2)
        cluster.list_page("v1", "ConfigMap", "default", limit=2,
                          continue_token=cont)  # consumes the token
        with pytest.raises(ob.Expired):
            cluster.list_page("v1", "ConfigMap", "default", limit=2,
                              continue_token=cont)


class TestStalePatch:
    def test_patch_with_stale_rv_is_409_over_http(self, client, server):
        cm = ob.new_object("v1", "ConfigMap", "sp", "default")
        cm["data"] = {"v": "1"}
        created = client.create(cm)
        stale_rv = ob.meta(created)["resourceVersion"]
        # someone else updates
        cur = client.get("v1", "ConfigMap", "sp", "default")
        cur["data"]["v"] = "2"
        client.update(cur)
        with pytest.raises(ob.Conflict):
            client.patch("v1", "ConfigMap", "sp",
                         {"metadata": {"resourceVersion": stale_rv},
                          "data": {"v": "3"}}, "default")
        # without the precondition the patch applies (merge semantics)
        out = client.patch("v1", "ConfigMap", "sp", {"data": {"v": "3"}},
                           "default")
        assert out["data"]["v"] == "3"


def test_continue_pages_report_snapshot_rv(server):
    """A watch resumed from a paginated list's rv must see objects
    created mid-pagination: every page carries the SNAPSHOT's rv."""
    cluster = server.cluster
    for i in range(6):
        cluster.create(ob.new_object("v1", "ConfigMap", f"s{i}", "default"))
    page1, cont, rv1 = cluster.list_page("v1", "ConfigMap", "default",
                                         limit=4)
    cluster.create(ob.new_object("v1", "ConfigMap", "mid-pagination",
                                 "default"))
    _page2, _cont2, rv2 = cluster.list_page("v1", "ConfigMap", "default",
                                            limit=4, continue_token=cont)
    assert rv2 == rv1  # pinned, NOT the post-creation current rv
    # resuming a watch from that rv replays the mid-pagination creation
    stream = cluster.watch("v1", "ConfigMap", "default", since_rv=rv2)
    names = []
    while True:
        ev = stream.poll()
        if ev is None:
            break
        names.append(ev.object["metadata"]["name"])
    stream.stop()
    assert "mid-pagination" in names


class TestServerSideApply:
    """Server-side apply over HTTP: fieldManager
    ownership, apply conflicts + force transfer, and declarative field
    removal — the apiserver behaviors CreateOrUpdate-style controllers
    assume (reference: notebook_controller.go:85 reconcile updates)."""

    AV, KIND = "kubeflow.org/v1", "Notebook"

    def _intent(self, **spec):
        return {"apiVersion": self.AV, "kind": self.KIND,
                "metadata": {"name": "nb", "namespace": "user1"},
                "spec": spec}

    def test_apply_creates_and_records_ownership(self, client):
        out = client.apply(self._intent(image="jax:0.8", replicas=1),
                           field_manager="ctrl")
        assert out["spec"] == {"image": "jax:0.8", "replicas": 1}
        mf = out["metadata"]["managedFields"]
        assert [e["manager"] for e in mf] == ["ctrl"]
        assert ["spec", "image"] in mf[0]["fields"]

    def test_disjoint_managers_coexist(self, client):
        client.apply(self._intent(image="jax:0.8"), field_manager="ctrl")
        out = client.apply(
            {"apiVersion": self.AV, "kind": self.KIND,
             "metadata": {"name": "nb", "namespace": "user1",
                          "labels": {"team": "ml"}}},
            field_manager="labeler")
        # both managers' fields persist, each owned separately
        assert out["spec"]["image"] == "jax:0.8"
        assert out["metadata"]["labels"] == {"team": "ml"}
        mgrs = {e["manager"] for e in out["metadata"]["managedFields"]}
        assert mgrs == {"ctrl", "labeler"}

    def test_conflicting_apply_is_409_until_forced(self, client):
        client.apply(self._intent(image="jax:0.8"), field_manager="ctrl")
        with pytest.raises(ob.Conflict, match="owned by ctrl"):
            client.apply(self._intent(image="jax:0.9"),
                         field_manager="intruder")
        # force transfers ownership; the original manager now conflicts
        out = client.apply(self._intent(image="jax:0.9"),
                           field_manager="intruder", force=True)
        assert out["spec"]["image"] == "jax:0.9"
        with pytest.raises(ob.Conflict, match="owned by intruder"):
            client.apply(self._intent(image="jax:1.0"),
                         field_manager="ctrl")

    def test_same_value_shares_ownership(self, client):
        client.apply(self._intent(image="jax:0.8"), field_manager="a")
        out = client.apply(self._intent(image="jax:0.8"),
                           field_manager="b")  # no conflict: same value
        owning = [e["manager"] for e in out["metadata"]["managedFields"]
                  if ["spec", "image"] in e["fields"]]
        assert sorted(owning) == ["a", "b"]
        # a drops the field from its intent; b still owns it -> retained
        out = client.apply(self._intent(), field_manager="a")
        assert out["spec"]["image"] == "jax:0.8"

    def test_dropped_field_is_removed(self, client):
        client.apply(self._intent(image="jax:0.8", replicas=2),
                     field_manager="ctrl")
        out = client.apply(self._intent(image="jax:0.8"),
                           field_manager="ctrl")
        # declarative removal: replicas no longer applied -> gone
        assert "replicas" not in out["spec"]

    def test_apply_does_not_steal_unowned_update_fields(self, client):
        client.apply(self._intent(image="jax:0.8"), field_manager="ctrl")
        # a status writer (plain update, no ownership) sets status
        cur = client.get(self.AV, self.KIND, "nb", "user1")
        cur["status"] = {"phase": "Running"}
        client.update_status(cur)
        # ctrl re-applies without status: status survives (unowned
        # fields are never removed)
        out = client.apply(self._intent(image="jax:0.8"),
                           field_manager="ctrl")
        assert out["status"] == {"phase": "Running"}

    def test_missing_field_manager_is_invalid_on_both_backends(self, client):
        # 422 round-trips to ob.Invalid so error handling is
        # backend-independent (same exception on FakeCluster directly)
        with pytest.raises(ob.Invalid):
            client.apply(self._intent(image="x"), field_manager="")
        with pytest.raises(ob.Invalid):
            FakeCluster().apply(self._intent(image="x"), field_manager="")

    def test_descendant_of_owned_leaf_conflicts(self, client):
        """Ownership guards the subtree: applying spec.resources.cpu
        under another manager's owned spec.resources scalar is a 409,
        not a silent clobber."""
        client.apply(self._intent(resources="small"), field_manager="a")
        deeper = {"apiVersion": self.AV, "kind": self.KIND,
                  "metadata": {"name": "nb", "namespace": "user1"},
                  "spec": {"resources": {"cpu": 2}}}
        with pytest.raises(ob.Conflict, match="owned by a"):
            client.apply(deeper, field_manager="b")
        out = client.apply(deeper, field_manager="b", force=True)
        assert out["spec"]["resources"] == {"cpu": 2}
        # ancestor direction: a's scalar would flatten b's map -> 409
        with pytest.raises(ob.Conflict, match="owned by b"):
            client.apply(self._intent(resources="small"),
                         field_manager="a")

    def test_map_owner_dropping_it_keeps_other_managers_entries(self, client):
        """A manager that owned only the map itself (spec: {}) and stops
        applying it must not wipe entries other managers own under it."""
        client.apply(self._intent(), field_manager="a")  # owns spec map
        client.apply(self._intent(image="jax:0.8"), field_manager="b")
        out = client.apply(
            {"apiVersion": self.AV, "kind": self.KIND,
             "metadata": {"name": "nb", "namespace": "user1"}},
            field_manager="a")  # a no longer applies spec at all
        assert out["spec"]["image"] == "jax:0.8"

    def test_fake_and_rest_identical(self, client, server):
        """The same apply sequence on FakeCluster directly and through
        HTTP produces identical objects (modulo uid/rv/timestamps)."""
        fake = FakeCluster()
        for backend in (fake, client):
            backend.apply(self._intent(image="jax:0.8", replicas=2),
                          field_manager="ctrl")
            backend.apply(
                {"apiVersion": self.AV, "kind": self.KIND,
                 "metadata": {"name": "nb", "namespace": "user1",
                              "labels": {"team": "ml"}}},
                field_manager="labeler")
            backend.apply(self._intent(image="jax:0.9"),
                          field_manager="ctrl")
        via_fake = fake.get(self.AV, self.KIND, "nb", "user1")
        via_rest = client.get(self.AV, self.KIND, "nb", "user1")
        for doc in (via_fake, via_rest):
            for k in ("uid", "creationTimestamp", "resourceVersion"):
                doc["metadata"].pop(k, None)
        assert via_fake == via_rest

    def test_sub_owner_removal_succeeds_under_map_assert(self, client):
        """The inverse of the map-owner case: b owns spec.image under
        a's spec map-assert; when b stops applying it, the field is
        REMOVED (an ancestor assert owns the map's existence, not the
        leaf — counting it as co-ownership would orphan the field
        forever)."""
        client.apply(self._intent(), field_manager="a")  # spec map assert
        client.apply(self._intent(image="jax:0.8"), field_manager="b")
        out = client.apply(self._intent(), field_manager="b")
        assert "image" not in (out.get("spec") or {})

    def test_reasserting_populated_map_composes(self, client):
        """Re-applying {spec: {}} against a spec that now has entries is
        NOT a conflict: asserting the map composes with deeper owners."""
        client.apply(self._intent(), field_manager="a")
        client.apply(self._intent(image="jax:0.8"), field_manager="b")
        out = client.apply(self._intent(), field_manager="a")  # no 409
        assert out["spec"]["image"] == "jax:0.8"

    def test_apply_body_url_mismatch_is_400(self, client):
        body = {"apiVersion": self.AV, "kind": self.KIND,
                "metadata": {"name": "OTHER", "namespace": "user1"},
                "spec": {"image": "x"}}
        import json as _json

        import requests

        r = requests.patch(
            client.base_url + "/apis/kubeflow.org/v1/namespaces/user1/"
            "notebooks/nb?fieldManager=ctrl",
            data=_json.dumps(body),
            headers={"Content-Type": "application/apply-patch+yaml"})
        assert r.status_code == 400
        # and nothing was applied anywhere
        assert client.get_or_none(self.AV, self.KIND, "OTHER", "user1") is None
        assert client.get_or_none(self.AV, self.KIND, "nb", "user1") is None

    def test_error_text_survives_non_dict_json_body(self, client, server):
        """A proxy answering 404 with a bare JSON string must still
        surface NotFound, not an AttributeError from .get on a str."""
        import pytest as _pytest

        class FakeResp:
            status_code = 404
            content = b'"not found"'
            text = '"not found"'

            def json(self):
                return "not found"

        orig = client._s.request
        client._s.request = lambda *a, **k: FakeResp()
        try:
            with _pytest.raises(ob.NotFound, match="not found"):
                client.get("v1", "ConfigMap", "x", "default")
        finally:
            client._s.request = orig
