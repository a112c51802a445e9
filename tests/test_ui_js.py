"""Execute the REAL web-UI JavaScript against the real backends.

Parity target: the reference drives its spawner through Selenium
(testing/test_jwa.py, 423 LoC of WebDriver against a live browser). This
container has no browser, so kubeflow_tpu/testing/jsdom.py rebuilds the
capability: the interpreter runs the exact `<script>` payloads served by
dashboard_ui.py / jwa_ui.py, with fetch() bridged into the same Router
objects production serves. Every flow below fails if the corresponding
UI JS breaks — the bar ("a test fails when the
registration-flow JS breaks").
"""

import pytest

from kubeflow_tpu.control.k8s import objects as ob
from kubeflow_tpu.control.k8s.fake import FakeCluster
from kubeflow_tpu.control.kfam.service import KfamService
from kubeflow_tpu.control.notebook import types as NT
from kubeflow_tpu.control.profile import types as PT
from kubeflow_tpu.testing.jsdom import Browser, JSObject, undefined
from kubeflow_tpu.webapps.dashboard import Dashboard
from kubeflow_tpu.webapps.dashboard_ui import PAGE as DASH_PAGE

USER = "alice@example.com"


def dash_browser(cluster) -> Browser:
    kfam = KfamService(cluster, cluster_admin="root@example.com")
    b = Browser(Dashboard(cluster, kfam=kfam).router())
    b.default_headers["kubeflow-userid"] = USER
    return b


class TestInterpreterCore:
    """Language-level sanity for the harness itself."""

    def test_core_semantics(self):
        b = Browser()
        b.load('<div id="out"></div>', run_scripts=False)
        b.run("""
          const xs = [3, 1, 2].map(x => x * 2).filter(x => x > 2);
          let s = `n=${xs.length}`;
          for (const [k, v] of Object.entries({a: 1})) s += ` ${k}${v}`;
          s += ' ' + (2 ** 10) + ' ' + (0.1).toFixed(2);
          s += ' ' + JSON.parse(JSON.stringify({z: [1, 2]})).z.join('-');
          document.getElementById('out').textContent = s;
        """)
        assert b.text("out") == "n=2 a1 1024 0.10 1-2"

    def test_async_await_and_rejection(self):
        b = Browser()
        b.load('<div id="out"></div>', run_scripts=False)
        b.run("""
          const api = () => Promise.reject(new Error('down'));
          async function go() {
            try { await api(); return 'unreachable'; }
            catch (e) { return 'caught:' + e.message; }
          }
          go().then(v => document.getElementById('out').textContent = v);
        """)
        assert b.text("out") == "caught:down"

    def test_unsupported_syntax_is_loud(self):
        from kubeflow_tpu.testing.jsdom import JSError

        b = Browser()
        with pytest.raises(JSError):
            b.run("class Foo { bar() {} }")


class TestDashboardRegistration:
    """The registration walkthrough — the reference's registration-page
    flow (centraldashboard public/components/registration-page.js)."""

    def test_fresh_user_sees_walkthrough_and_creates_profile(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        b.load(DASH_PAGE)
        # no namespaces -> walkthrough visible at step 0
        assert b.by_id("register").style.get("display") == "block"
        steps = b.document.querySelectorAll("#register .step")
        active = [s.dataset.get("step") for s in steps
                  if "active" in s.className.split()]
        assert active == ["0"]

        b.click("reg-start")
        # invalid name: error shown, next disabled
        b.type_into("reg-ns", "Bad_Name!")
        assert b.text("reg-err") == "invalid namespace name"
        assert b.by_id("reg-next").disabled is True
        # valid name enables next
        b.type_into("reg-ns", "alice-ns")
        assert b.text("reg-err") == ""
        assert b.by_id("reg-next").disabled is False
        b.click("reg-next")
        assert b.text("reg-confirm-name") == "alice-ns"
        assert b.text("reg-confirm-user") == USER

        b.click("reg-create")
        # the REAL backend created the Profile CR
        prof = cluster.get(PT.API_VERSION, PT.KIND, "alice-ns")
        assert PT.owner_name(prof) == USER
        active = [s.dataset.get("step")
                  for s in b.document.querySelectorAll("#register .step")
                  if "active" in s.className.split()]
        assert active == ["4"]  # finished panel

    def test_create_failure_surfaces_error_and_offers_retry(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        # a Profile squatting on the name makes create fail server-side
        squat = ob.new_object(PT.API_VERSION, PT.KIND, "taken")
        squat["spec"] = {"owner": {"kind": "User", "name": "bob@example.com"}}
        cluster.create(squat)
        b.load(DASH_PAGE)
        b.click("reg-start")
        b.type_into("reg-ns", "taken")
        b.click("reg-next")
        b.click("reg-create")
        assert "failed:" in b.text("reg-msg")
        assert b.by_id("reg-retry").style.get("display") == ""
        # retry returns to the name step instead of dead-ending
        b.click("reg-retry")
        active = [s.dataset.get("step")
                  for s in b.document.querySelectorAll("#register .step")
                  if "active" in s.className.split()]
        assert active == ["1"]

    def test_existing_member_skips_walkthrough_and_loads_cards(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        prof = ob.new_object(PT.API_VERSION, PT.KIND, "alice-ns")
        prof["spec"] = {"owner": {"kind": "User", "name": USER}}
        cluster.create(prof)
        cluster.create(ob.new_object("v1", "Namespace", "alice-ns"))
        b.load(DASH_PAGE)
        assert b.by_id("register").style.get("display") in (None, "", "none")
        sel = b.by_id("ns")
        assert [o.value for o in sel.options] == ["alice-ns"]
        # namespace cards were fetched for the selected namespace
        assert ("GET", "/api/activities/alice-ns") in b.requests
        assert ("GET", "/api/workgroup/get-contributors/alice-ns") in b.requests


class TestDashboardContributors:
    def _member_browser(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        prof = ob.new_object(PT.API_VERSION, PT.KIND, "alice-ns")
        prof["spec"] = {"owner": {"kind": "User", "name": USER}}
        cluster.create(prof)
        cluster.create(ob.new_object("v1", "Namespace", "alice-ns"))
        b.load(DASH_PAGE)
        return cluster, b

    def test_add_and_remove_contributor_through_ui(self):
        cluster, b = self._member_browser()
        b.type_into("contrib-email", "bob@example.com")
        b.click("contrib-add")
        # rendered AND persisted (kfam wrote the RoleBinding)
        assert "bob@example.com" in b.by_id("contributors").textContent
        rbs = [rb for rb in cluster.list("rbac.authorization.k8s.io/v1",
                                         "RoleBinding", "alice-ns")
               if ob.annotations_of(rb).get(PT.ANNO_USER) == "bob@example.com"]
        assert rbs, "contributor RoleBinding not created"
        # remove via the row button the JS built
        rows = b.by_id("contributors").querySelectorAll("button")
        assert len(rows) == 1
        rows[0].click()
        assert "owner only" in b.by_id("contributors").textContent
        rbs = [rb for rb in cluster.list("rbac.authorization.k8s.io/v1",
                                         "RoleBinding", "alice-ns")
               if ob.annotations_of(rb).get(PT.ANNO_USER) == "bob@example.com"]
        assert not rbs

    def test_invalid_contributor_shows_error_not_crash(self):
        cluster, b = self._member_browser()
        b.type_into("contrib-email", "not-an-email")
        b.click("contrib-add")
        assert b.text("contrib-err") != ""
        assert "not-an-email" not in b.by_id("contributors").textContent


class TestDashboardServingCard:
    def test_unreachable_serving_distinct_from_no_models(self):
        """The ADVICE r2 fix, executed: a failed fetch must render
        'serving unreachable', an empty inventory 'no models'."""
        cluster = FakeCluster()
        kfam = KfamService(cluster, cluster_admin="root@example.com")

        def boom(url):
            raise OSError("connection refused")

        b = Browser(Dashboard(cluster, kfam=kfam, fetch_json=boom).router())
        b.default_headers["kubeflow-userid"] = USER
        b.load(DASH_PAGE)
        assert "serving unreachable" in b.by_id("served").textContent

        ok = Browser(Dashboard(cluster, kfam=kfam,
                               fetch_json=lambda u: {"models": []}).router())
        ok.default_headers["kubeflow-userid"] = USER
        ok.load(DASH_PAGE)
        assert "no models" in ok.by_id("served").textContent


class TestJwaSpawner:
    """The spawner flow the reference verifies with Selenium
    (testing/test_jwa.py): fill the form, launch, see it listed."""

    def _browser(self):
        from kubeflow_tpu.webapps.jwa import JupyterWebApp

        cluster = FakeCluster()
        prof = ob.new_object(PT.API_VERSION, PT.KIND, "team-a")
        prof["spec"] = {"owner": {"kind": "User", "name": USER}}
        cluster.create(prof)
        cluster.create(ob.new_object("v1", "Namespace", "team-a"))
        from kubeflow_tpu.webapps.jwa_ui import PAGE as JWA_PAGE

        b = Browser(JupyterWebApp(cluster).router())
        b.default_headers["kubeflow-userid"] = USER
        b.load(JWA_PAGE)
        return cluster, b

    def test_spawn_notebook_through_real_form(self):
        cluster, b = self._browser()
        # init() populated the selectors from api/config + api/namespaces
        assert [o.value for o in b.by_id("ns").options] == ["team-a"]
        assert len(b.by_id("images").options) >= 1
        name_input = b.by_id("spawn").querySelector('[name]')
        assert name_input.name == "name"
        name_input.value = "my-notebook"
        b.submit("spawn")
        nb = cluster.get(NT.API_VERSION, NT.KIND,
                         "my-notebook", "team-a")
        assert nb is not None
        # the listing refreshed and shows the new notebook
        assert "my-notebook" in b.by_id("list").textContent

    def test_invalid_name_rejected_by_backend_shown_in_ui(self):
        cluster, b = self._browser()
        b.by_id("spawn").querySelector('[name]').value = "Invalid Name!"
        b.submit("spawn")
        assert b.text("msg") != ""
        assert not cluster.list(NT.API_VERSION, NT.KIND,
                                namespace="team-a")

    def test_poddefault_checkboxes_flow_into_spawn(self):
        from kubeflow_tpu.control.poddefault import new_poddefault

        cluster, b = self._browser()
        cluster.create(new_poddefault(
            "tpu-access", "team-a", desc="Mount TPU libs",
            selector={"matchLabels": {"inject-tpu": "true"}}))
        # re-select the namespace so the poddefault list reloads
        b.select("ns", "team-a")
        boxes = b.by_id("poddefaults").querySelectorAll("input")
        assert len(boxes) == 1
        boxes[0].checked = True
        b.by_id("spawn").querySelector('[name]').value = "pd-notebook"
        b.submit("spawn")
        nb = cluster.get(NT.API_VERSION, NT.KIND,
                         "pd-notebook", "team-a")
        labels = (((nb["spec"].get("template") or {}).get("metadata") or {})
                  .get("labels") or {})
        assert labels.get("inject-tpu") == "true"


class TestBackendNameValidation:
    """Server-side validation the harness forced into existence: the
    browser regex is advisory; the backends must 400 invalid names."""

    def test_workgroup_create_rejects_invalid_namespace(self):
        from kubeflow_tpu.utils.httpd import HttpReq

        cluster = FakeCluster()
        b = dash_browser(cluster)
        b.load(DASH_PAGE, run_scripts=False)
        import json as _j

        req = HttpReq(method="POST", path="/api/workgroup/create", params={},
                      query={}, headers={"kubeflow-userid": USER},
                      body=_j.dumps({"namespace": "Bad_Name!"}).encode())
        resp = b.routers[-1][1].dispatch(req)
        assert resp.status == 400
        assert not cluster.list(PT.API_VERSION, PT.KIND)

    def test_nonstring_notebook_name_is_400_not_500(self):
        from kubeflow_tpu.webapps.jwa import JupyterWebApp
        from kubeflow_tpu.utils.httpd import HttpReq
        import json as _j

        cluster = FakeCluster()
        r = JupyterWebApp(cluster).router()
        req = HttpReq(method="POST", path="/api/namespaces/ns/notebooks",
                      params={}, query={}, headers={},
                      body=_j.dumps({"name": 123}).encode())
        assert r.dispatch(req).status == 400

    def test_derived_fallback_name_is_sanitized(self):
        from kubeflow_tpu.utils.names import sanitize_dns1123

        assert sanitize_dns1123("Alice.B") == "alice-b"
        assert sanitize_dns1123("---") == "user"


class TestWorkgroupSettingsCard:
    """Admin all-namespaces view + the nuke-self danger-zone flow
    (reference: namespace-selector all-namespaces + manage-workgroup)."""

    def test_admin_sees_all_namespaces_list(self):
        cluster = FakeCluster()
        kfam = KfamService(cluster, cluster_admin=USER)  # alice IS admin
        for n in ("team-a", "team-b"):
            cluster.create(ob.new_object("v1", "Namespace", n))
        b = Browser(Dashboard(cluster, kfam=kfam).router())
        b.default_headers["kubeflow-userid"] = USER
        b.load(DASH_PAGE)
        assert b.by_id("admin-ns").style.get("display") == "block"
        assert "team-a" in b.by_id("all-ns").textContent
        assert "team-b" in b.by_id("all-ns").textContent

    def test_non_admin_card_stays_hidden(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)  # admin is root@, not alice
        b.load(DASH_PAGE)
        assert b.by_id("admin-ns").style.get("display") in (None, "none")

    def test_nuke_flow_requires_confirmation_and_deletes_profiles(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        prof = ob.new_object(PT.API_VERSION, PT.KIND, "alice-ns")
        prof["spec"] = {"owner": {"kind": "User", "name": USER}}
        cluster.create(prof)
        cluster.create(ob.new_object("v1", "Namespace", "alice-ns"))
        b.load(DASH_PAGE)
        # cancel path: nothing deleted
        b.click("nuke-btn")
        assert b.by_id("nuke-confirm").style.get("display") == ""
        b.click("nuke-no")
        assert cluster.get_or_none(PT.API_VERSION, PT.KIND, "alice-ns")
        # confirm path: profiles gone, UI returns to the walkthrough
        b.click("nuke-btn")
        b.click("nuke-yes")
        assert cluster.get_or_none(PT.API_VERSION, PT.KIND, "alice-ns") is None
        assert "deleted 1" in b.text("nuke-msg")
        assert b.by_id("register").style.get("display") == "block"


class TestDashboardNavigation:
    """Hash routing + iframe app embedding (the reference SPA's
    iframe-based app navigation, main-page.js routing)."""

    def test_hash_routes_to_iframe_and_back(self):
        cluster = FakeCluster()
        b = dash_browser(cluster)
        prof = ob.new_object(PT.API_VERSION, PT.KIND, "alice-ns")
        prof["spec"] = {"owner": {"kind": "User", "name": USER}}
        cluster.create(prof)
        cluster.create(ob.new_object("v1", "Namespace", "alice-ns"))
        b.load(DASH_PAGE)
        main = b.document.querySelector("main")
        assert main.style.get("display") in (None, "")
        # navigate to an embedded app route
        routes = b.eval("Object.keys(APP_ROUTES)")
        assert routes, "dashboard defines no APP_ROUTES"
        target = routes[0]
        b.set_hash(target)
        assert main.style.get("display") == "none"
        frame = b.by_id("app-frame")
        assert frame.getAttribute("src")
        assert "ns=alice-ns" in frame.getAttribute("src")
        # active nav link follows the hash
        active = [a.getAttribute("href")
                  for a in b.document.querySelectorAll("#appnav a")
                  if "active" in a.className.split()]
        assert active == [target]
        # unknown route -> 404 view, never a blank page
        b.set_hash("#/bogus")
        assert b.by_id("notfound-view").style.get("display") == ""
        assert b.text("notfound-path") == "#/bogus"
        # home again
        b.set_hash("#/")
        assert main.style.get("display") == ""


class TestTensorboardsUi:
    """The Tensorboards CRUD app's page executed end to end (a consumer
    of crud_backend the reference never shipped a frontend for)."""

    def _browser(self):
        from kubeflow_tpu.webapps.tensorboards import PAGE, TensorboardsApp

        cluster = FakeCluster()
        cluster.create(ob.new_object("v1", "Namespace", "team-a"))
        b = Browser(TensorboardsApp(cluster).router())
        b.default_headers["kubeflow-userid"] = USER
        b.location["search"] = "?ns=team-a"
        b.load(PAGE)
        return cluster, b

    def test_create_list_delete_roundtrip(self):
        cluster, b = self._browser()
        assert "none yet" in b.by_id("rows").textContent
        b.by_id("name").value = "exp1"
        b.by_id("logspath").value = "gs://bkt/logs"
        b.click("create")
        tb = cluster.get("tensorboard.kubeflow.org/v1alpha1", "Tensorboard",
                         "exp1", "team-a")
        assert tb["spec"]["logspath"] == "gs://bkt/logs"
        assert "exp1" in b.by_id("rows").textContent
        # delete through the row button the JS built
        btns = b.by_id("rows").querySelectorAll("button")
        assert len(btns) == 1
        btns[0].click()
        assert cluster.get_or_none("tensorboard.kubeflow.org/v1alpha1",
                                   "Tensorboard", "exp1", "team-a") is None
        assert "none yet" in b.by_id("rows").textContent

    def test_invalid_inputs_surface_backend_errors(self):
        cluster, b = self._browser()
        b.by_id("name").value = "Bad Name!"
        b.by_id("logspath").value = "gs://bkt/logs"
        b.click("create")
        assert "invalid" in b.text("err")
        b.by_id("name").value = "ok-name"
        b.by_id("logspath").value = "relative/path"
        b.click("create")
        assert b.text("err")  # logspath must be cloud or absolute
        assert not cluster.list("tensorboard.kubeflow.org/v1alpha1",
                                "Tensorboard", namespace="team-a")


class TestHarnessSemantics:
    """JS-semantics corners where silent divergence from a browser would
    make UI tests lie (found by the jsdom-focused review)."""

    def _out(self, js):
        b = Browser()
        b.load('<div id="out"></div>', run_scripts=False)
        b.run(js)
        return b, b.text("out")

    def test_reference_identity_equality(self):
        _, out = self._out("""
          const a = [1, 2], b = [1, 2], o = {x: 1}, p = {x: 1};
          document.getElementById('out').textContent =
            [a === b, a === a, o === p, o == p, [o].includes(p),
             [o].includes(o)].join(',');
        """)
        assert out == "false,true,false,false,false,true"

    def test_unhandled_async_rejection_fails_the_test(self):
        from kubeflow_tpu.testing.jsdom import JSThrow

        b = Browser()
        b.load('<button id="go"></button>', run_scripts=False)
        b.run("""
          document.getElementById('go').addEventListener('click',
            async () => { throw new Error('broken handler'); });
        """)
        with pytest.raises(JSThrow, match="broken handler"):
            b.click("go")
        # top-level rejected chain also surfaces
        with pytest.raises(JSThrow, match="boom"):
            b.run("Promise.reject(new Error('boom'));")

    def test_cleared_timers_do_not_fire(self):
        b = Browser()
        b.load('<div id="out">0</div>', run_scripts=False)
        b.run("""
          let n = 0;
          const keep = setInterval(() => { n += 1; }, 1000);
          const kill = setInterval(() => { n += 100; }, 1000);
          clearInterval(kill);
          const once = setTimeout(() => { n += 10; }, 50);
          const never = setTimeout(() => { n += 1000; }, 50);
          clearTimeout(never);
          document.getElementById('out').textContent = 'armed';
          setInterval(() => {
            document.getElementById('out').textContent = String(n); }, 1);
        """)
        b.fire_timers()  # intervals render before timeouts drain
        assert b.text("out") == "1"  # keep fired; cleared interval didn't
        b.fire_timers()
        # n = keep(1) + once(10) + keep(1) = 12: the one-shot fired
        # exactly once, nothing cleared ever fired
        assert b.text("out") == "12"

    def test_regex_global_flag_and_groups(self):
        _, out = self._out("""
          const s = 'a-a-a'.replace(/a/g, 'b');
          const t = 'v1.2'.replace(/(\\d+)\\.(\\d+)/, '$2:$1');
          document.getElementById('out').textContent = s + ' ' + t;
        """)
        assert out == "b-b-b v2:1"

    def test_split_and_modulo_and_infinity(self):
        _, out = self._out("""
          document.getElementById('out').textContent =
            ['a b'.split().length, 'abc'.split('').join('|'),
             'a, b,c'.split(/,\\s*/).join('+'),
             (-5) % 3, '' + 1 / 0].join(' ');
        """)
        assert out == "1 a|b|c a+b+c -2 Infinity"

    def test_eval_rejects_trailing_tokens(self):
        from kubeflow_tpu.testing.jsdom import JSError

        b = Browser()
        b.load("<div></div>", run_scripts=False)
        with pytest.raises(JSError, match="trailing"):
            b.eval("1 + 1 garbage")

    def test_typeof_propagates_real_errors(self):
        from kubeflow_tpu.testing.jsdom import JSThrow

        b = Browser()
        b.load('<div id="out"></div>', run_scripts=False)
        b.run("""document.getElementById('out').textContent =
                   typeof neverDeclared;""")
        assert b.text("out") == "undefined"
        with pytest.raises(JSThrow):
            b.run("const o = {}; typeof o.missing.deep;")
