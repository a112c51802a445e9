"""jsdom conformance: the semantics the harness GUARANTEES, pinned.

The web UIs are tested by executing their real <script> payloads in
kubeflow_tpu/testing/jsdom.py — a second implementation of JS semantics
(the reference uses Selenium against real browsers,
testing/test_jwa.py:17-24; this container has no browser). A divergence
between this harness and a real engine is invisible to every UI test, so
this file is the contract: each test pins a spec edge
case the UIs rely on, and each KNOWN DEVIATION from real-engine behavior
is asserted AS the deviant behavior — if the harness's semantics drift,
these tests fail loudly instead of the UI tests silently meaning
something else.

Guaranteed (spec-conformant):
  - event bubbling order target -> ancestors; stopPropagation halts
    before the next ancestor but not the current node's listeners;
    removeEventListener detaches.
  - FormData(form): unchecked checkboxes omitted, checked included;
    <select> contributes the selected option's value.
  - async/await: awaited rejections route to catch; an async function's
    return value resolves the caller's promise; Promise chaining maps
    values through .then.
  - Promise.all resolves with ordered results.
  - microtask queue (round 5): .then callbacks defer to
    the microtask checkpoint ('sync,then' order, as real engines);
    fetch settles on the macrotask queue in request order.

Known deviations (asserted as such):
  - setTimeout/setInterval NEVER auto-fire: callbacks queue until the
    test driver calls Browser.fire_timers() (jest-fake-timer model);
    one-shots drain, intervals refire per call.
  - an async function runs to completion before its CALLER resumes
    (`await` drains the loop cooperatively instead of suspending a
    continuation) — caller-vs-continuation interleavings are the one
    ordering class still unobservable.
  - addEventListener's capture argument is ignored (no capture phase).
"""

from kubeflow_tpu.testing.jsdom import Browser


def run(html, script):
    b = Browser()
    b.load(html + '<div id="out"></div>', run_scripts=False)
    b.run(script)
    return b


OUT = "document.getElementById('out').textContent = log.join(',');"


class TestEventBubbling:
    def test_bubbles_target_then_ancestors(self):
        b = run('<div id="o"><p id="m"><button id="i">x</button></p></div>', """
          let log = [];
          for (const id of ['o', 'm', 'i'])
            document.getElementById(id).addEventListener('click', () => log.push(id));
          document.getElementById('i').click();
        """ + OUT)
        assert b.text("out") == "i,m,o"

    def test_stop_propagation_halts_ancestors_not_siblings(self):
        b = run('<div id="o"><button id="i">x</button></div>', """
          let log = [];
          document.getElementById('o').addEventListener('click', () => log.push('outer'));
          const el = document.getElementById('i');
          el.addEventListener('click', (e) => { log.push('a'); e.stopPropagation(); });
          el.addEventListener('click', () => log.push('b'));
          el.click();
        """ + OUT)
        assert b.text("out") == "a,b"

    def test_remove_event_listener(self):
        b = run('<button id="i">x</button>', """
          let log = [];
          const el = document.getElementById('i');
          const h = () => log.push('h');
          el.addEventListener('click', h);
          el.click();
          el.removeEventListener('click', h);
          el.click();
        """ + OUT)
        assert b.text("out") == "h"


class TestFormData:
    def test_checkbox_and_select_semantics(self):
        b = run("""
          <form id="f">
            <input name="a" value="1">
            <input type="checkbox" name="unchecked" value="u">
            <input type="checkbox" name="checked" value="c" checked>
            <select name="s"><option value="x">x</option>
              <option value="y" selected>y</option></select>
          </form>""", """
          let log = [];
          for (const [k, v] of new FormData(document.getElementById('f')).entries())
            log.push(k + '=' + v);
        """ + OUT)
        assert b.text("out") == "a=1,checked=c,s=y"


class TestAsync:
    def test_await_rejection_routes_to_catch(self):
        b = run("", """
          let log = [];
          const api = () => Promise.reject(new Error('down'));
          async function go() {
            try { await api(); log.push('unreachable'); }
            catch (e) { log.push('caught:' + e.message); }
            return 'done';
          }
          go().then(v => { log.push(v); """ + OUT + """ });
        """)
        assert b.text("out") == "caught:down,done"

    def test_then_chaining_maps_values(self):
        b = run("", """
          let log = [];
          Promise.resolve(2).then(v => v * 3).then(v => log.push('v' + v));
        """ + OUT)
        # real-engine order: OUT runs at script end, BEFORE the deferred
        # then callbacks; after the drain the chain has mapped 2*3
        assert b.text("out") == ""
        assert b.eval("log.join(',')") == "v6"

    def test_promise_all_ordered(self):
        b = run("", """
          let log = [];
          Promise.all([Promise.resolve('a'), Promise.resolve('b')])
            .then(vs => log.push(vs.join('+')));
        """ + OUT)
        assert b.eval("log.join(',')") == "a+b"

    def test_microtask_queue_defers_then(self):
        """The regression the round-4 review asked for: under round-4's EAGER
        resolution this ordered 'then,sync' and the real-engine order
        was untestable by construction; the event loop restores
        'sync,then' (script to completion, then microtask checkpoint)."""
        b = run("", """
          let log = [];
          Promise.resolve(1).then(() => log.push('then'));
          log.push('sync');
        """ + OUT)
        assert b.text("out") == "sync"  # script-end snapshot
        assert b.eval("log.join(',')") == "sync,then"

    def test_fetch_handlers_run_after_sync_code_in_request_order(self):
        """The fetch-then-render interleaving class Selenium catches in
        the reference (test_jwa.py state waits): two back-to-back
        fetches settle on the macrotask queue — after ALL sync code, in
        request order."""
        from kubeflow_tpu.utils.httpd import Router, json_resp

        r = Router()
        r.route("GET", "/slow", lambda req: json_resp({"v": "slow"}))
        r.route("GET", "/fast", lambda req: json_resp({"v": "fast"}))
        b = Browser(r)
        b.load('<div id="out"></div>', run_scripts=False)
        b.run("""
          window.log = [];
          fetch('/slow').then(r => r.json()).then(d => window.log.push(d.v));
          fetch('/fast').then(r => r.json()).then(d => window.log.push(d.v));
          window.log.push('sync');
        """)
        assert b.eval("window.log.join(',')") == "sync,slow,fast"


class TestKnownDeviations:
    """Real engines behave differently HERE. These tests pin the
    harness's actual model so drift is loud; UI scripts must not depend
    on the real-engine order for these."""

    def test_timers_fire_only_via_fire_timers(self):
        b = Browser()
        b.load('<div id="out"></div>', run_scripts=False)
        flush = ("document.getElementById('out').textContent = "
                 "window.log.join(',');")
        b.run("""
          window.log = [];
          setTimeout(() => window.log.push('once'), 0);
          setInterval(() => window.log.push('tick'), 1000);
          window.log.push('sync');
        """)
        b.run(flush)
        assert b.text("out") == "sync"          # nothing auto-fired
        b.fire_timers()
        b.run(flush)
        assert b.text("out") == "sync,tick,once"
        b.fire_timers()                          # one-shot drained
        b.run(flush)
        assert b.text("out") == "sync,tick,once,tick"


class TestRejectionIsolation:
    def test_orphaned_rejection_fails_the_same_browser_not_the_next(self):
        """A rejection created during an eval expression (after the
        pre-drain) must surface in THIS browser's eval — and must never
        leak into an unrelated Browser created afterwards."""
        import pytest

        from kubeflow_tpu.testing.jsdom import JSThrow

        b1 = Browser()
        b1.load("<div></div>", run_scripts=False)
        with pytest.raises(JSThrow):
            b1.eval("[Promise.reject('boom'), 2][1]")
        b2 = Browser()
        b2.load("<div></div>", run_scripts=False)
        b2.run("let y = 1;")  # must not re-raise b1's rejection
        assert b2.eval("y") == 1
