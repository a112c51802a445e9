"""A minimal JavaScript interpreter + DOM for executing the web UIs in tests.

The reference drives its spawner UI through real browsers with Selenium
(testing/test_jwa.py — 423 LoC of WebDriver). This container has no
browser and no node, so the capability is rebuilt as infrastructure: a
tree-walking interpreter for the ES2017 subset the in-tree UIs use
(arrow functions, async/await over a real microtask/macrotask event
loop — see EventLoop, template literals, for-of with array
destructuring, try/catch, regex literals, spread) plus
a DOM with enough fidelity for the pages (createElement/appendChild,
getElementById, querySelectorAll with tag/#id/.class/descendant and
:checked, innerHTML parse/serialize, event listeners, forms/FormData)
and a `fetch` bridged straight into a platform Router.

Tests execute the REAL `<script>` payloads served by
webapps/dashboard_ui.py and jwa_ui.py against the real backends: a test
fails when the registration-flow JS breaks.

This is NOT a general JS engine. Unsupported syntax raises JSError at
parse time, loudly; growing the subset is preferable to silently
mis-executing.
"""

from __future__ import annotations

import html.parser
import json as _json
import re as _re
from typing import Any

# ---------------------------------------------------------------------------
# values


class JSUndefined:
    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "undefined"

    def __bool__(self):
        return False


undefined = JSUndefined()


class JSError(Exception):
    """Parse/runtime error in the harness itself."""


class JSThrow(Exception):
    """A JS `throw`: .value is the thrown JS value."""

    def __init__(self, value):
        super().__init__(js_str(value))
        self.value = value


class JSObject(dict):
    """Plain JS object: property bag."""


def new_error(message) -> JSObject:
    return JSObject({"name": "Error", "message": message})


class JSFunction:
    def __init__(self, params, body, env, interp, *, is_arrow=False,
                 is_async=False, name="", is_expr_body=False):
        self.params = params        # list of (name, default|None, rest:bool)
        self.body = body
        self.env = env
        self.interp = interp
        self.is_arrow = is_arrow
        self.is_async = is_async
        self.name = name
        self.is_expr_body = is_expr_body

    def call(self, args, this=undefined):
        return self.interp.call_function(self, args, this)


class EventLoop:
    """Microtask + macrotask queues (the round-3
    harness resolved promises eagerly, so `await`/`then` ordering races
    in the very fetch-then-render flows the UI tests exercise were
    untestable by construction). The harness drains at every entry point
    (script run, user action, timer fire), and `await` on a pending
    promise drains until it settles — handler ordering follows queue
    discipline, matching what Selenium observes against a real browser
    (reference: testing/test_jwa.py state-transition waits)."""

    def __init__(self):
        import collections

        self.microtasks = collections.deque()
        self.macrotasks = collections.deque()
        # rejected promises born on THIS loop (scoped per interpreter:
        # a rejection leaking past one Browser's last drain must not
        # fail an unrelated Browser's next entry point)
        self.unhandled: list["JSPromise"] = []

    def microtask(self, fn) -> None:
        self.microtasks.append(fn)

    def macrotask(self, fn) -> None:
        self.macrotasks.append(fn)

    def _step(self) -> bool:
        if self.microtasks:
            self.microtasks.popleft()()
            return True
        if self.macrotasks:
            self.macrotasks.popleft()()
            return True
        return False

    def drain(self) -> None:
        while self._step():
            pass

    def drain_until(self, done) -> None:
        while not done():
            if not self._step():
                raise JSError("await on a promise that can never settle "
                              "(event loop exhausted)")


# Rejected promises register at settle time — on their loop when known,
# else here; _handled flips when a reaction (then/catch/finally/await)
# attaches. Harness entry points call check_unhandled_rejections() after
# draining — an unhandled rejection must FAIL the test, not vanish (the
# harness's worst failure mode).
_UNHANDLED_REJECTIONS: list["JSPromise"] = []


def check_unhandled_rejections(loop: "EventLoop | None" = None) -> None:
    pend = [p for p in _UNHANDLED_REJECTIONS if not p._handled]
    _UNHANDLED_REJECTIONS.clear()
    if loop is not None:
        pend += [p for p in loop.unhandled if not p._handled]
        loop.unhandled.clear()
    if pend:
        raise JSThrow(pend[0].error)


class JSPromise:
    """Promise with a real pending state. Internal producers may still
    construct settled promises directly; every CONSUMER (then/catch/
    finally/await/Promise.all) defers its reactions through the event
    loop, so ordering is queue-driven, never eager."""

    PENDING, OK, ERR = 0, 1, 2

    def __init__(self, value=undefined, error=None, loop=None):
        self.state = self.ERR if error is not None else self.OK
        self.value = value
        self.error = error  # a JSThrow-able value or None
        self._callbacks: list = []  # (fn, loop) pairs awaiting settle
        self._handled = False
        self._loop: EventLoop | None = loop
        if self.state == self.ERR:
            self._register_rejection()

    def _register_rejection(self) -> None:
        (self._loop.unhandled if self._loop is not None
         else _UNHANDLED_REJECTIONS).append(self)

    @property
    def rejected(self):
        return self.state == self.ERR

    @classmethod
    def make_pending(cls, loop: "EventLoop | None" = None) -> "JSPromise":
        p = cls(loop=loop)
        p.state = cls.PENDING
        p.value = undefined
        p.error = None
        return p

    def on_settle(self, cb, loop: EventLoop) -> None:
        if self.state == self.PENDING:
            self._callbacks.append((cb, loop))
        else:
            loop.microtask(cb)

    def _flush(self) -> None:
        for cb, loop in self._callbacks:
            loop.microtask(cb)
        self._callbacks.clear()

    def settle_ok(self, v) -> None:
        if self.state != self.PENDING:
            return
        self.state, self.value = self.OK, v
        self._flush()

    def settle_err(self, e) -> None:
        if self.state != self.PENDING:
            return
        self.state, self.error = self.ERR, e
        self._register_rejection()
        self._flush()

    @staticmethod
    def resolve(v):
        if isinstance(v, JSPromise):
            return v
        return JSPromise(value=v)

    @staticmethod
    def reject(e, loop: "EventLoop | None" = None):
        return JSPromise(error=e, loop=loop)


def _call1(handler, arg):
    """Invoke a JS or python callback with one argument."""
    return handler.call([arg]) if isinstance(handler, JSFunction) \
        else handler(arg)


def _adopt(out: JSPromise, v, loop: EventLoop) -> None:
    """Settle `out` from a handler's return value, unwrapping promises
    (thenable adoption)."""
    if isinstance(v, JSPromise):
        v._handled = True

        def chain():
            if v.state == JSPromise.ERR:
                out.settle_err(v.error)
            else:
                out.settle_ok(v.value)

        v.on_settle(chain, loop)
    else:
        out.settle_ok(v)


def _then(p: JSPromise, on_ok, on_err, loop: EventLoop) -> JSPromise:
    """The one deferred reaction primitive: then/catch/finally and
    Promise.all all reduce to it."""
    p._handled = True
    out = JSPromise.make_pending(loop)

    def react():
        if p.state == JSPromise.ERR:
            if on_err is None:
                out.settle_err(p.error)
                return
            try:
                _adopt(out, _call1(on_err, p.error), loop)
            except JSThrow as t:
                out.settle_err(t.value)
        else:
            if on_ok is None:
                out.settle_ok(p.value)
                return
            try:
                _adopt(out, _call1(on_ok, p.value), loop)
            except JSThrow as t:
                out.settle_err(t.value)

    p.on_settle(react, loop)
    return out


def _raise_if_rejected(v):
    """Entry-point guard for values handed back to the harness: a
    settled-rejected promise raises immediately. Pending promises pass
    through — the caller drains the loop and
    check_unhandled_rejections() catches what settles rejected."""
    if isinstance(v, JSPromise) and v.rejected:
        v._handled = True
        raise JSThrow(v.error)
    return v


# ---------------------------------------------------------------------------
# lexer

_KEYWORDS = {
    "const", "let", "var", "function", "return", "if", "else", "for", "of",
    "in", "while", "break", "continue", "try", "catch", "finally", "throw",
    "new", "typeof", "async", "await", "true", "false", "null", "undefined",
    "delete", "instanceof", "do",
    # recognized only to FAIL loudly at parse time (unsupported subset)
    "class", "switch", "case", "extends", "super", "yield",
}

_PUNCT = [
    "...", "===", "!==", "**=", ">>>", "=>", "==", "!=", "<=", ">=", "&&",
    "||", "??", "?.", "++", "--", "+=", "-=", "*=", "/=", "%=", "**",
    "(", ")",
    "{", "}", "[", "]", ";", ",", ".", "?", ":", "=", "+", "-", "*", "/",
    "%", "<", ">", "!", "&", "|", "^", "~",
]

# tokens after which a `/` starts a REGEX literal, not division
_REGEX_PRECEDERS = {
    "=", "(", ",", "[", "{", ";", ":", "?", "&&", "||", "!", "==", "===",
    "!=", "!==", "return", "=>", "+", "typeof", "new", "throw",
}


def tokenize(src: str):
    toks: list[tuple[str, Any]] = []  # (kind, value); kind: num str tmpl re id kw punct
    i, n = 0, len(src)
    while i < n:
        c = src[i]
        if c in " \t\r\n":
            i += 1
            continue
        if src.startswith("//", i):
            j = src.find("\n", i)
            i = n if j < 0 else j
            continue
        if src.startswith("/*", i):
            j = src.find("*/", i)
            if j < 0:
                raise JSError("unterminated block comment")
            i = j + 2
            continue
        if c.isdigit() or (c == "." and i + 1 < n and src[i + 1].isdigit()):
            m = _re.match(r"\d*\.?\d+(?:[eE][+-]?\d+)?", src[i:])
            text = m.group(0)
            toks.append(("num", float(text) if ("." in text or "e" in text
                                               or "E" in text) else int(text)))
            i += len(text)
            continue
        if c in "'\"":
            j, out = i + 1, []
            while j < n and src[j] != c:
                if src[j] == "\\":
                    out.append(_unescape(src[j + 1]))
                    j += 2
                else:
                    out.append(src[j])
                    j += 1
            if j >= n:
                raise JSError("unterminated string")
            toks.append(("str", "".join(out)))
            i = j + 1
            continue
        if c == "`":
            parts, j, buf = [], i + 1, []  # parts: ("str", s) | ("expr", toks)
            while j < n and src[j] != "`":
                if src[j] == "\\":
                    buf.append(_unescape(src[j + 1]))
                    j += 2
                elif src.startswith("${", j):
                    parts.append(("str", "".join(buf)))
                    buf = []
                    depth, k = 1, j + 2
                    while k < n and depth:
                        if src[k] == "{":
                            depth += 1
                        elif src[k] == "}":
                            depth -= 1
                        k += 1
                    parts.append(("expr", tokenize(src[j + 2:k - 1])))
                    j = k
                else:
                    buf.append(src[j])
                    j += 1
            if j >= n:
                raise JSError("unterminated template literal")
            parts.append(("str", "".join(buf)))
            toks.append(("tmpl", parts))
            i = j + 1
            continue
        if c == "/" and _regex_ok(toks):
            j, in_cls = i + 1, False
            while j < n:
                if src[j] == "\\":
                    j += 2
                    continue
                if src[j] == "[":
                    in_cls = True
                elif src[j] == "]":
                    in_cls = False
                elif src[j] == "/" and not in_cls:
                    break
                j += 1
            if j >= n:
                raise JSError("unterminated regex literal")
            body = src[i + 1:j]
            k = j + 1
            while k < n and src[k].isalpha():
                k += 1
            toks.append(("re", (body, src[j + 1:k])))
            i = k
            continue
        if c.isalpha() or c in "_$":
            m = _re.match(r"[A-Za-z_$][A-Za-z0-9_$]*", src[i:])
            word = m.group(0)
            toks.append(("kw" if word in _KEYWORDS else "id", word))
            i += len(word)
            continue
        for p in _PUNCT:
            if src.startswith(p, i):
                toks.append(("punct", p))
                i += len(p)
                break
        else:
            raise JSError(f"unexpected character {c!r} at {i}")
    toks.append(("eof", None))
    return toks


def _unescape(c: str) -> str:
    return {"n": "\n", "t": "\t", "r": "\r", "0": "\0"}.get(c, c)


def _regex_ok(toks) -> bool:
    for kind, val in reversed(toks):
        return kind in ("punct", "kw") and val in _REGEX_PRECEDERS
    return True  # start of input


# ---------------------------------------------------------------------------
# parser (Pratt for expressions, recursive descent for statements)


class Parser:
    def __init__(self, toks):
        self.toks = toks
        self.i = 0

    def peek(self, k=0):
        return self.toks[min(self.i + k, len(self.toks) - 1)]

    def at(self, kind, val=None):
        t = self.peek()
        return t[0] == kind and (val is None or t[1] == val)

    def eat(self, kind, val=None):
        if not self.at(kind, val):
            raise JSError(f"expected {kind} {val!r}, got {self.peek()!r} "
                          f"(tok {self.i})")
        t = self.toks[self.i]
        self.i += 1
        return t

    def opt(self, kind, val=None):
        if self.at(kind, val):
            self.i += 1
            return True
        return False

    # -- statements ---------------------------------------------------------

    def parse_program(self):
        body = []
        while not self.at("eof"):
            body.append(self.statement())
        return ("block", body)

    def statement(self):
        if self.opt("punct", ";"):
            return ("empty",)
        if self.at("punct", "{"):
            return self.block()
        if self.at("kw", "const") or self.at("kw", "let") or self.at("kw", "var"):
            s = self.var_decl()
            self.opt("punct", ";")
            return s
        if self.at("kw", "function") or (
                self.at("kw", "async") and self.peek(1) == ("kw", "function")):
            is_async = self.opt("kw", "async")
            self.eat("kw", "function")
            name = self.eat("id")[1]
            fn = self.function_rest(is_async=is_async, name=name)
            return ("fundecl", name, fn)
        if self.opt("kw", "if"):
            self.eat("punct", "(")
            cond = self.expression()
            self.eat("punct", ")")
            then = self.statement()
            alt = self.statement() if self.opt("kw", "else") else None
            return ("if", cond, then, alt)
        if self.opt("kw", "while"):
            self.eat("punct", "(")
            cond = self.expression()
            self.eat("punct", ")")
            return ("while", cond, self.statement())
        if self.opt("kw", "for"):
            return self.for_stmt()
        if self.opt("kw", "return"):
            if self.at("punct", ";") or self.at("punct", "}") or self.at("eof"):
                self.opt("punct", ";")
                return ("return", None)
            e = self.expression()
            self.opt("punct", ";")
            return ("return", e)
        if self.opt("kw", "throw"):
            e = self.expression()
            self.opt("punct", ";")
            return ("throw", e)
        if self.opt("kw", "break"):
            self.opt("punct", ";")
            return ("break",)
        if self.opt("kw", "continue"):
            self.opt("punct", ";")
            return ("continue",)
        if self.opt("kw", "try"):
            block = self.block()
            param, handler, fin = None, None, None
            if self.opt("kw", "catch"):
                if self.opt("punct", "("):
                    param = self.eat("id")[1]
                    self.eat("punct", ")")
                handler = self.block()
            if self.opt("kw", "finally"):
                fin = self.block()
            return ("try", block, param, handler, fin)
        e = self.expression()
        self.opt("punct", ";")
        return ("expr", e)

    def block(self):
        self.eat("punct", "{")
        body = []
        while not self.at("punct", "}"):
            body.append(self.statement())
        self.eat("punct", "}")
        return ("block", body)

    def var_decl(self):
        kind = self.eat("kw")[1]
        decls = []
        while True:
            decls.append(self.binding())
            if not self.opt("punct", ","):
                break
        return ("var", kind, decls)

    def binding(self):
        """(target, init): target is ('id', name) or ('arr', [names])."""
        if self.opt("punct", "["):
            names = []
            while not self.at("punct", "]"):
                names.append(self.eat("id")[1])
                if not self.opt("punct", ","):
                    break
            self.eat("punct", "]")
            target = ("arr", names)
        else:
            target = ("id", self.eat("id")[1])
        init = self.assignment() if self.opt("punct", "=") else None
        return (target, init)

    def for_stmt(self):
        self.eat("punct", "(")
        # for (const x of e) / for (const [a,b] of e) / classic for(;;)
        if self.at("kw", "const") or self.at("kw", "let") or self.at("kw", "var"):
            save = self.i
            self.eat("kw")
            if self.opt("punct", "["):
                names = []
                while not self.at("punct", "]"):
                    names.append(self.eat("id")[1])
                    if not self.opt("punct", ","):
                        break
                self.eat("punct", "]")
                target = ("arr", names)
            else:
                target = ("id", self.eat("id")[1])
            if self.opt("kw", "of"):
                iterable = self.expression()
                self.eat("punct", ")")
                return ("forof", target, iterable, self.statement())
            self.i = save  # classic for with declaration init
        init = None
        if not self.at("punct", ";"):
            if self.at("kw", "const") or self.at("kw", "let") or self.at("kw", "var"):
                init = self.var_decl()
            else:
                init = ("expr", self.expression())
        self.eat("punct", ";")
        cond = None if self.at("punct", ";") else self.expression()
        self.eat("punct", ";")
        step = None if self.at("punct", ")") else self.expression()
        self.eat("punct", ")")
        return ("for", init, cond, step, self.statement())

    # -- functions ----------------------------------------------------------

    def function_rest(self, is_async: bool, name: str = ""):
        self.eat("punct", "(")
        params = self.param_list()
        body = self.block()
        return ("func", params, body, is_async, False, name, False)

    def param_list(self):
        params = []
        while not self.at("punct", ")"):
            rest = self.opt("punct", "...")
            pname = self.eat("id")[1]
            default = self.assignment() if self.opt("punct", "=") else None
            params.append((pname, default, rest))
            if not self.opt("punct", ","):
                break
        self.eat("punct", ")")
        return params

    # -- expressions --------------------------------------------------------

    def expression(self):
        e = self.assignment()
        while self.at("punct", ","):
            # comma operator is rare in the UIs; treat as sequence
            self.eat("punct", ",")
            e = ("seq", e, self.assignment())
        return e

    def assignment(self):
        if self._arrow_ahead():
            return self.arrow()
        left = self.ternary()
        for op in ("=", "+=", "-=", "*=", "/=", "%="):
            if self.at("punct", op):
                self.eat("punct", op)
                right = self.assignment()
                return ("assign", op, left, right)
        return left

    def _arrow_ahead(self) -> bool:
        """Lookahead: `x =>`, `async x =>`, `(...) =>`, `async (...) =>`."""
        j = self.i
        if self.toks[j] == ("kw", "async"):
            j += 1
        t = self.toks[j]
        if t[0] == "id" and self.toks[j + 1] == ("punct", "=>"):
            return True
        if t == ("punct", "("):
            depth = 0
            while j < len(self.toks):
                tk = self.toks[j]
                if tk == ("punct", "("):
                    depth += 1
                elif tk == ("punct", ")"):
                    depth -= 1
                    if depth == 0:
                        return self.toks[j + 1] == ("punct", "=>")
                elif tk[0] == "eof":
                    return False
                j += 1
        return False

    def arrow(self):
        is_async = self.opt("kw", "async")
        if self.at("id"):
            params = [(self.eat("id")[1], None, False)]
        else:
            self.eat("punct", "(")
            params = self.param_list()
        self.eat("punct", "=>")
        if self.at("punct", "{"):
            body = self.block()
            return ("func", params, body, is_async, True, "", False)
        body = self.assignment()
        return ("func", params, body, is_async, True, "", True)

    def ternary(self):
        cond = self.nullish()
        if self.opt("punct", "?"):
            a = self.assignment()
            self.eat("punct", ":")
            b = self.assignment()
            return ("cond", cond, a, b)
        return cond

    def nullish(self):
        e = self.logic_or()
        while self.opt("punct", "??"):
            e = ("nullish", e, self.logic_or())
        return e

    def logic_or(self):
        e = self.logic_and()
        while self.opt("punct", "||"):
            e = ("or", e, self.logic_and())
        return e

    def logic_and(self):
        e = self.equality()
        while self.opt("punct", "&&"):
            e = ("and", e, self.equality())
        return e

    def equality(self):
        e = self.relational()
        while True:
            for op in ("===", "!==", "==", "!="):
                if self.at("punct", op):
                    self.eat("punct", op)
                    e = ("bin", op, e, self.relational())
                    break
            else:
                return e

    def relational(self):
        e = self.additive()
        while True:
            for op in ("<=", ">=", "<", ">"):
                if self.at("punct", op):
                    self.eat("punct", op)
                    e = ("bin", op, e, self.additive())
                    break
            else:
                if self.opt("kw", "instanceof"):
                    e = ("bin", "instanceof", e, self.additive())
                    continue
                if self.opt("kw", "in"):
                    e = ("bin", "in", e, self.additive())
                    continue
                return e

    def additive(self):
        e = self.multiplicative()
        while self.at("punct", "+") or self.at("punct", "-"):
            op = self.eat("punct")[1]
            e = ("bin", op, e, self.multiplicative())
        return e

    def multiplicative(self):
        e = self.exponent()
        while self.at("punct", "*") or self.at("punct", "/") or self.at("punct", "%"):
            op = self.eat("punct")[1]
            e = ("bin", op, e, self.exponent())
        return e

    def exponent(self):
        e = self.unary()
        if self.at("punct", "**"):
            self.eat("punct", "**")
            return ("bin", "**", e, self.exponent())  # right-assoc
        return e

    def unary(self):
        if self.at("punct", "!"):
            self.eat("punct", "!")
            return ("not", self.unary())
        if self.at("punct", "-"):
            self.eat("punct", "-")
            return ("neg", self.unary())
        if self.at("punct", "+"):
            self.eat("punct", "+")
            return ("tonum", self.unary())
        if self.opt("kw", "typeof"):
            return ("typeof", self.unary())
        if self.opt("kw", "await"):
            return ("await", self.unary())
        if self.opt("kw", "delete"):
            return ("delete", self.unary())
        if self.opt("kw", "new"):
            callee = self.member_chain(self.primary(), no_call=True)
            args = []
            if self.opt("punct", "("):
                args = self.arguments()
            # member/call chains continue off the constructed object:
            # new FormData(f).entries()
            return self.member_chain(("new", callee, args))
        if self.at("punct", "++") or self.at("punct", "--"):
            op = self.eat("punct")[1]
            return ("preinc", op, self.unary())
        e = self.postfix()
        return e

    def postfix(self):
        e = self.member_chain(self.primary())
        if self.at("punct", "++") or self.at("punct", "--"):
            op = self.eat("punct")[1]
            return ("postinc", op, e)
        return e

    def member_chain(self, e, no_call=False):
        while True:
            if self.opt("punct", "."):
                e = ("member", e, self.eat_name(), False)
            elif self.opt("punct", "?."):
                e = ("member", e, self.eat_name(), True)
            elif self.opt("punct", "["):
                idx = self.expression()
                self.eat("punct", "]")
                e = ("index", e, idx)
            elif not no_call and self.at("punct", "("):
                self.eat("punct", "(")
                e = ("call", e, self.arguments())
            else:
                return e

    def eat_name(self) -> str:
        t = self.peek()
        if t[0] in ("id", "kw"):
            self.i += 1
            return t[1]
        raise JSError(f"expected property name, got {t!r}")

    def arguments(self):
        args = []
        while not self.at("punct", ")"):
            if self.opt("punct", "..."):
                args.append(("spread", self.assignment()))
            else:
                args.append(self.assignment())
            if not self.opt("punct", ","):
                break
        self.eat("punct", ")")
        return args

    def primary(self):
        t = self.peek()
        if t[0] == "num" or t[0] == "str":
            self.i += 1
            return ("lit", t[1])
        if t[0] == "re":
            self.i += 1
            return ("regex", t[1])
        if t[0] == "tmpl":
            self.i += 1
            parts = []
            for kind, payload in t[1]:
                if kind == "str":
                    parts.append(("lit", payload))
                else:
                    parts.append(Parser(payload).expression())
            return ("tmplexpr", parts)
        if t == ("kw", "true"):
            self.i += 1
            return ("lit", True)
        if t == ("kw", "false"):
            self.i += 1
            return ("lit", False)
        if t == ("kw", "null"):
            self.i += 1
            return ("lit", None)
        if t == ("kw", "undefined"):
            self.i += 1
            return ("lit", undefined)
        if t == ("kw", "function") or (
                t == ("kw", "async") and self.peek(1) == ("kw", "function")):
            is_async = self.opt("kw", "async")
            self.eat("kw", "function")
            name = self.eat("id")[1] if self.at("id") else ""
            return self.function_rest(is_async=is_async, name=name)
        if t == ("punct", "("):
            self.eat("punct", "(")
            e = self.expression()
            self.eat("punct", ")")
            return e
        if t == ("punct", "["):
            self.eat("punct", "[")
            items = []
            while not self.at("punct", "]"):
                if self.opt("punct", "..."):
                    items.append(("spread", self.assignment()))
                else:
                    items.append(self.assignment())
                if not self.opt("punct", ","):
                    break
            self.eat("punct", "]")
            return ("array", items)
        if t == ("punct", "{"):
            self.eat("punct", "{")
            props = []
            while not self.at("punct", "}"):
                if self.opt("punct", "..."):
                    props.append(("spread", self.assignment()))
                elif self.at("punct", "["):
                    self.eat("punct", "[")
                    key = self.expression()
                    self.eat("punct", "]")
                    self.eat("punct", ":")
                    props.append((("computed", key), self.assignment()))
                else:
                    kt = self.peek()
                    if kt[0] in ("id", "kw", "str", "num"):
                        self.i += 1
                        key = str(kt[1])
                    else:
                        raise JSError(f"bad object key {kt!r}")
                    if self.opt("punct", ":"):
                        props.append((key, self.assignment()))
                    elif self.at("punct", "("):  # method shorthand
                        props.append((key, self.function_rest(is_async=False,
                                                              name=key)))
                    else:  # shorthand {a}
                        props.append((key, ("name", key)))
                if not self.opt("punct", ","):
                    break
            self.eat("punct", "}")
            return ("object", props)
        if t[0] == "id":
            self.i += 1
            return ("name", t[1])
        raise JSError(f"unexpected token {t!r}")


# ---------------------------------------------------------------------------
# control-flow signals


class _Return(Exception):
    def __init__(self, value):
        self.value = value


class _Break(Exception):
    pass


class _Continue(Exception):
    pass


# ---------------------------------------------------------------------------
# interpreter


def js_truthy(v) -> bool:
    if v is undefined or v is None:
        return False
    if isinstance(v, bool):
        return v
    if isinstance(v, (int, float)):
        return v != 0
    if isinstance(v, str):
        return v != ""
    return True


def js_str(v) -> str:
    if v is undefined:
        return "undefined"
    if v is None:
        return "null"
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float):
        if v != v:
            return "NaN"
        if v == float("inf"):
            return "Infinity"
        if v == float("-inf"):
            return "-Infinity"
        if v == int(v):
            return str(int(v))
        return repr(v)
    if isinstance(v, list):
        return ",".join("" if x is undefined or x is None else js_str(x)
                        for x in v)
    if isinstance(v, JSObject):
        if "message" in v and v.get("name") == "Error":
            return f"Error: {js_str(v['message'])}"
        return "[object Object]"
    return str(v)


def js_num(v) -> float:
    if isinstance(v, bool):
        return 1 if v else 0
    if isinstance(v, (int, float)):
        return v
    if isinstance(v, str):
        s = v.strip()
        if s == "":
            return 0
        try:
            return int(s)
        except ValueError:
            try:
                return float(s)
            except ValueError:
                return float("nan")
    if v is None:
        return 0
    return float("nan")


def js_eq_loose(a, b) -> bool:
    if (a is None or a is undefined) and (b is None or b is undefined):
        return True
    if a is None or a is undefined or b is None or b is undefined:
        return False
    if isinstance(a, (dict, list)) or isinstance(b, (dict, list)):
        return a is b  # loose == on two objects is still identity in JS
    if type(a) is type(b) or (isinstance(a, (int, float))
                              and isinstance(b, (int, float))):
        return a == b
    return js_num(a) == js_num(b)


class Env:
    __slots__ = ("vars", "parent")

    def __init__(self, parent=None):
        self.vars: dict[str, Any] = {}
        self.parent = parent

    def get(self, name):
        e = self
        while e is not None:
            if name in e.vars:
                return e.vars[name]
            e = e.parent
        raise JSThrow(new_error(f"{name} is not defined"))

    def set(self, name, value):
        e = self
        while e is not None:
            if name in e.vars:
                e.vars[name] = value
                return
            e = e.parent
        # implicit global (sloppy mode)
        root = self
        while root.parent is not None:
            root = root.parent
        root.vars[name] = value

    def declare(self, name, value):
        self.vars[name] = value


class Interpreter:
    def __init__(self, global_env: Env):
        self.genv = global_env
        self.loop = EventLoop()

    # -- function invocation ------------------------------------------------

    def call_function(self, fn, args, this=undefined):
        if callable(fn) and not isinstance(fn, JSFunction):
            return fn(*args)
        env = Env(fn.env)
        if not fn.is_arrow:  # arrows keep the LEXICAL this
            env.declare("this", this)
            env.declare("arguments", list(args))
        for i, (pname, default, rest) in enumerate(fn.params):
            if rest:
                env.declare(pname, list(args[i:]))
                break
            v = args[i] if i < len(args) else undefined
            if v is undefined and default is not None:
                v = self.eval(default, env)
            env.declare(pname, v)

        def run():
            if fn.is_expr_body:
                return self.eval(fn.body, env)
            try:
                self.exec(fn.body, env)
            except _Return as r:
                return r.value
            return undefined

        if fn.is_async:
            try:
                return JSPromise.resolve(run())
            except JSThrow as t:
                return JSPromise.reject(t.value, self.loop)
        return run()

    def make_function(self, node, env):
        _, params, body, is_async, is_arrow, name, is_expr = node
        return JSFunction(params, body, env, self, is_arrow=is_arrow,
                          is_async=is_async, name=name, is_expr_body=is_expr)

    # -- statements ---------------------------------------------------------

    def exec(self, node, env):
        op = node[0]
        if op == "block":
            benv = Env(env)
            # function declarations hoist within the block
            for s in node[1]:
                if s[0] == "fundecl":
                    benv.declare(s[1], self.make_function(s[2], benv))
            for s in node[1]:
                self.exec(s, benv)
        elif op == "expr":
            _raise_if_rejected(self.eval(node[1], env))
        elif op == "var":
            for target, init in node[2]:
                v = self.eval(init, env) if init is not None else undefined
                self._bind(target, v, env)
        elif op == "fundecl":
            pass  # hoisted in block
        elif op == "if":
            if js_truthy(self.eval(node[1], env)):
                self.exec(node[2], env)
            elif node[3] is not None:
                self.exec(node[3], env)
        elif op == "while":
            while js_truthy(self.eval(node[1], env)):
                try:
                    self.exec(node[2], env)
                except _Break:
                    break
                except _Continue:
                    continue
        elif op == "for":
            fenv = Env(env)
            if node[1] is not None:
                self.exec(node[1], fenv)
            while node[2] is None or js_truthy(self.eval(node[2], fenv)):
                try:
                    self.exec(node[4], fenv)
                except _Break:
                    break
                except _Continue:
                    pass
                if node[3] is not None:
                    self.eval(node[3], fenv)
        elif op == "forof":
            it = self.eval(node[2], env)
            if isinstance(it, JSObject):
                raise JSThrow(new_error("object is not iterable"))
            if it is undefined or it is None:
                raise JSThrow(new_error("iterable is null/undefined"))
            for item in list(it):
                fenv = Env(env)
                self._bind(node[1], item, fenv)
                try:
                    self.exec(node[3], fenv)
                except _Break:
                    break
                except _Continue:
                    continue
        elif op == "return":
            raise _Return(self.eval(node[1], env)
                          if node[1] is not None else undefined)
        elif op == "throw":
            raise JSThrow(self.eval(node[1], env))
        elif op == "break":
            raise _Break()
        elif op == "continue":
            raise _Continue()
        elif op == "try":
            _, block, param, handler, fin = node
            try:
                try:
                    self.exec(block, env)
                except JSThrow as t:
                    if handler is None:
                        raise
                    henv = Env(env)
                    if param:
                        henv.declare(param, t.value)
                    self.exec(handler, henv)
            finally:
                if fin is not None:
                    self.exec(fin, env)
        elif op == "empty":
            pass
        else:
            raise JSError(f"unknown statement {op}")

    def _bind(self, target, value, env):
        if target[0] == "id":
            env.declare(target[1], value)
        else:  # ("arr", names)
            seq = value if isinstance(value, (list, tuple)) else []
            for k, nm in enumerate(target[1]):
                env.declare(nm, seq[k] if k < len(seq) else undefined)

    # -- expressions --------------------------------------------------------

    def eval(self, node, env):
        op = node[0]
        if op == "lit":
            return node[1]
        if op == "name":
            return env.get(node[1])
        if op == "tmplexpr":
            return "".join(js_str(self.eval(p, env)) for p in node[1])
        if op == "regex":
            body, flags = node[1]
            return JSRegExp(body, flags)
        if op == "array":
            out = []
            for item in node[1]:
                if item[0] == "spread":
                    out.extend(list(self.eval(item[1], env)))
                else:
                    out.append(self.eval(item, env))
            return out
        if op == "object":
            o = JSObject()
            for key, vexpr in node[1]:
                if key == "spread":
                    src = self.eval(vexpr, env)
                    if isinstance(src, dict):
                        o.update(src)
                    continue
                if isinstance(key, tuple) and key[0] == "computed":
                    key = js_str(self.eval(key[1], env))
                o[key] = self.eval(vexpr, env)
            return o
        if op == "func":
            return self.make_function(node, env)
        if op == "seq":
            self.eval(node[1], env)
            return self.eval(node[2], env)
        if op == "cond":
            return (self.eval(node[2], env) if js_truthy(self.eval(node[1], env))
                    else self.eval(node[3], env))
        if op == "or":
            v = self.eval(node[1], env)
            return v if js_truthy(v) else self.eval(node[2], env)
        if op == "and":
            v = self.eval(node[1], env)
            return self.eval(node[2], env) if js_truthy(v) else v
        if op == "nullish":
            v = self.eval(node[1], env)
            return self.eval(node[2], env) if v is None or v is undefined else v
        if op == "not":
            return not js_truthy(self.eval(node[1], env))
        if op == "neg":
            return -js_num(self.eval(node[1], env))
        if op == "tonum":
            return js_num(self.eval(node[1], env))
        if op == "typeof":
            try:
                v = self.eval(node[1], env)
            except JSThrow:
                # JS only special-cases an unresolvable *reference*;
                # typeof obj.missing.deep must propagate the TypeError
                if node[1][0] == "name":
                    return "undefined"
                raise
            if v is undefined:
                return "undefined"
            if v is None:
                return "object"
            if isinstance(v, bool):
                return "boolean"
            if isinstance(v, (int, float)):
                return "number"
            if isinstance(v, str):
                return "string"
            if isinstance(v, JSFunction) or callable(v):
                return "function"
            return "object"
        if op == "await":
            v = self.eval(node[1], env)
            if isinstance(v, JSPromise):
                if v.state == JSPromise.PENDING:
                    # cooperative await: run OTHER queued reactions until
                    # this promise settles — the interleaving real async
                    # code observes (note the enclosing async fn still
                    # runs to completion before its caller resumes; true
                    # continuation suspension is out of scope)
                    self.loop.drain_until(
                        lambda: v.state != JSPromise.PENDING)
                v._handled = True
                if v.state == JSPromise.ERR:
                    raise JSThrow(v.error)
                return v.value
            return v
        if op == "delete":
            t = node[1]
            if t[0] == "member":
                obj = self.eval(t[1], env)
                if isinstance(obj, dict):
                    obj.pop(t[2], None)
            elif t[0] == "index":
                obj = self.eval(t[1], env)
                key = self.eval(t[2], env)
                if isinstance(obj, dict):
                    obj.pop(js_str(key), None)
            return True
        if op == "bin":
            return self._binop(node[1], node[2], node[3], env)
        if op == "assign":
            return self._assign(node[1], node[2], node[3], env)
        if op in ("preinc", "postinc"):
            delta = 1 if node[1] == "++" else -1
            old = js_num(self.eval(node[2], env))
            self._assign("=", node[2], ("lit", old + delta), env)
            return old + delta if op == "preinc" else old
        if op == "member":
            obj = self.eval(node[1], env)
            if node[3] and (obj is undefined or obj is None):
                return undefined
            return self.get_member(obj, node[2])
        if op == "index":
            obj = self.eval(node[1], env)
            key = self.eval(node[2], env)
            if isinstance(obj, list) and isinstance(key, (int, float)):
                k = int(key)
                return obj[k] if 0 <= k < len(obj) else undefined
            if isinstance(obj, str) and isinstance(key, (int, float)):
                k = int(key)
                return obj[k] if 0 <= k < len(obj) else undefined
            return self.get_member(obj, js_str(key))
        if op == "call":
            return self._call(node, env)
        if op == "new":
            ctor = self.eval(node[1], env)
            args = [self.eval(a, env) for a in node[2]]
            if isinstance(ctor, JSFunction):
                this = JSObject()
                r = ctor.call(args, this=this)
                return r if isinstance(r, (JSObject, list)) else this
            if callable(ctor):
                return ctor(*args)
            raise JSThrow(new_error("not a constructor"))
        raise JSError(f"unknown expression {op}")

    def _binop(self, op, ln, rn, env):
        a = self.eval(ln, env)
        b = self.eval(rn, env)
        if op == "+":
            if isinstance(a, str) or isinstance(b, str) or \
                    isinstance(a, (list, JSObject)) or isinstance(b, (list, JSObject)):
                return js_str(a) + js_str(b)
            return js_num(a) + js_num(b)
        if op == "-":
            return js_num(a) - js_num(b)
        if op == "*":
            return js_num(a) * js_num(b)
        if op == "/":
            d = js_num(b)
            if d == 0:
                return float("inf") if js_num(a) > 0 else float("-inf") \
                    if js_num(a) < 0 else float("nan")
            return js_num(a) / d
        if op == "%":
            d = js_num(b)
            if d == 0:
                return float("nan")
            import math

            return math.fmod(js_num(a), d)  # JS takes the dividend's sign
        if op == "**":
            return js_num(a) ** js_num(b)
        if op == "===":
            return self._strict_eq(a, b)
        if op == "!==":
            return not self._strict_eq(a, b)
        if op == "==":
            return js_eq_loose(a, b)
        if op == "!=":
            return not js_eq_loose(a, b)
        if op in ("<", ">", "<=", ">="):
            if isinstance(a, str) and isinstance(b, str):
                pass
            else:
                a, b = js_num(a), js_num(b)
            return {"<": a < b, ">": a > b, "<=": a <= b, ">=": a >= b}[op]
        if op == "instanceof":
            return isinstance(a, JSObject) and isinstance(b, (JSFunction,)) \
                or (b is self.genv.vars.get("Error")
                    and isinstance(a, JSObject) and a.get("name") == "Error")
        if op == "in":
            return js_str(a) in b if isinstance(b, dict) else False
        raise JSError(f"unknown binop {op}")

    @staticmethod
    def _strict_eq(a, b):
        if isinstance(a, bool) != isinstance(b, bool):
            return False
        if a is undefined or a is None or b is undefined or b is None:
            return a is b
        # JS === is reference identity for objects/arrays/functions
        if isinstance(a, (dict, list, JSFunction)) or \
                isinstance(b, (dict, list, JSFunction)):
            return a is b
        return a == b

    def _assign(self, op, left, rnode, env):
        value = self.eval(rnode, env)
        if op != "=":
            cur = self.eval(left, env)
            base = {"+=": "+", "-=": "-", "*=": "*", "/=": "/", "%=": "%"}[op]
            value = self._binop(base, ("lit", cur), ("lit", value), env)
        if left[0] == "name":
            env.set(left[1], value)
        elif left[0] == "member":
            obj = self.eval(left[1], env)
            self.set_member(obj, left[2], value)
        elif left[0] == "index":
            obj = self.eval(left[1], env)
            key = self.eval(left[2], env)
            if isinstance(obj, list) and isinstance(key, (int, float)):
                k = int(key)
                while len(obj) <= k:
                    obj.append(undefined)
                obj[k] = value
            else:
                self.set_member(obj, js_str(key), value)
        else:
            raise JSError(f"bad assignment target {left[0]}")
        return value

    def _call(self, node, env):
        _, callee, argnodes = node
        args = []
        for a in argnodes:
            if a[0] == "spread":
                args.extend(list(self.eval(a[1], env)))
            else:
                args.append(self.eval(a, env))
        # method call: bind `this`
        if callee[0] == "member":
            obj = self.eval(callee[1], env)
            if callee[3] and (obj is undefined or obj is None):
                return undefined
            fn = self.get_member(obj, callee[2])
            if fn is undefined:
                raise JSThrow(new_error(
                    f"{callee[2]} is not a function on {type(obj).__name__}"))
            if isinstance(fn, JSFunction):
                return fn.call(args, this=obj)
            return fn(*args)
        fn = self.eval(callee, env)
        if isinstance(fn, JSFunction):
            return fn.call(args)
        if callable(fn):
            return fn(*args)
        raise JSThrow(new_error("not a function"))

    # -- member access (builtin method tables) ------------------------------

    def get_member(self, obj, name):
        if obj is undefined or obj is None:
            raise JSThrow(new_error(
                f"cannot read property {name!r} of {js_str(obj)}"))
        if isinstance(obj, JSPromise):
            return _promise_member(obj, name, self)
        if isinstance(obj, str):
            return _string_member(obj, name)
        if isinstance(obj, list):
            return _array_member(obj, name, self)
        if isinstance(obj, (int, float)) and not isinstance(obj, bool):
            return _number_member(obj, name)
        if isinstance(obj, JSRegExp):
            return getattr(obj, name)
        if isinstance(obj, JSObject):
            if name in obj:
                return obj[name]
            return undefined
        if isinstance(obj, dict):
            return obj.get(name, undefined)
        # host objects (DOM elements, fetch responses, ...) expose
        # python attributes/properties directly
        try:
            return getattr(obj, name)
        except AttributeError:
            return undefined

    def set_member(self, obj, name, value):
        if isinstance(obj, dict):
            obj[name] = value
            return
        setattr(obj, name, value)


class JSRegExp:
    def __init__(self, body, flags):
        self.source = body
        if isinstance(flags, str):
            unknown = set(flags) - set("gims")
            if unknown:
                raise JSError(f"unsupported regex flags {''.join(unknown)!r}")
            self.global_ = "g" in flags
            pyflags = (_re.IGNORECASE if "i" in flags else 0) | \
                (_re.MULTILINE if "m" in flags else 0) | \
                (_re.DOTALL if "s" in flags else 0)
        else:  # legacy int flags
            self.global_ = False
            pyflags = flags
        self._rx = _re.compile(_js_regex_to_py(body), pyflags)

    def test(self, s=""):
        return self._rx.search(js_str(s)) is not None

    def exec(self, s=""):
        m = self._rx.search(js_str(s))
        if m is None:
            return None
        return [m.group(0)] + [g if g is not None else undefined
                               for g in m.groups()]


def _js_regex_to_py(body: str) -> str:
    # the UI regexes are plain ERE-compatible; pass through
    return body


def _string_member(s: str, name):
    simple = {
        "length": len(s),
    }
    if name in simple:
        return simple[name]
    table = {
        "trim": lambda: s.strip(),
        "toLowerCase": lambda: s.lower(),
        "toUpperCase": lambda: s.upper(),
        "includes": lambda sub="": js_str(sub) in s,
        "startsWith": lambda sub="": s.startswith(js_str(sub)),
        "endsWith": lambda sub="": s.endswith(js_str(sub)),
        "indexOf": lambda sub="": s.find(js_str(sub)),
        "slice": lambda a=0, b=None: s[_slice(a, b, len(s))],
        "substring": lambda a=0, b=None: s[_slice(a, b, len(s))],
        "split": lambda sep=undefined: _js_split(s, sep),
        "replace": lambda pat, rep: (
            pat._rx.sub(_js_replacement(rep), s,
                        count=0 if pat.global_ else 1)
            if isinstance(pat, JSRegExp) else s.replace(js_str(pat),
                                                        js_str(rep), 1)),
        "replaceAll": lambda pat, rep: (
            pat._rx.sub(_js_replacement(rep), s)
            if isinstance(pat, JSRegExp)
            else s.replace(js_str(pat), js_str(rep))),
        "charAt": lambda i=0: s[int(i)] if 0 <= int(i) < len(s) else "",
        "repeat": lambda k: s * int(k),
        "padStart": lambda w, c=" ": s.rjust(int(w), js_str(c)),
        "match": lambda rx: rx.exec(s) if isinstance(rx, JSRegExp) else None,
        "concat": lambda *a: s + "".join(js_str(x) for x in a),
        "toString": lambda: s,
    }
    if name in table:
        return table[name]
    return undefined


def _js_split(s: str, sep):
    if sep is undefined:
        return [s]  # JS no-arg split does NOT char the string
    if isinstance(sep, JSRegExp):
        return sep._rx.split(s)
    sep = js_str(sep)
    if sep == "":
        return list(s)
    return s.split(sep)


def _js_replacement(rep) -> str:
    """JS $n/$& replacement tokens -> Python re templates."""
    out = _re.sub(r"\$(\d+)", r"\\\1", js_str(rep))
    out = out.replace("$&", "\\g<0>")
    return out


def _slice(a, b, n):
    a = int(js_num(a)) if a is not None and a is not undefined else 0
    if a < 0:
        a += n
    if b is None or b is undefined:
        return slice(max(a, 0), None)
    b = int(js_num(b))
    if b < 0:
        b += n
    return slice(max(a, 0), max(b, 0))


def _array_member(arr: list, name, interp):
    def call(f, *a):
        return f.call(list(a)) if isinstance(f, JSFunction) else f(*a)

    if name == "length":
        return len(arr)
    table = {
        "push": lambda *a: (arr.extend(a), len(arr))[1],
        "pop": lambda: arr.pop() if arr else undefined,
        "shift": lambda: arr.pop(0) if arr else undefined,
        "unshift": lambda *a: (arr.__setitem__(slice(0, 0), list(a)),
                               len(arr))[1],
        "map": lambda f: [call(f, v, i) for i, v in enumerate(arr)],
        "filter": lambda f: [v for i, v in enumerate(arr)
                             if js_truthy(call(f, v, i))],
        "forEach": lambda f: ([call(f, v, i) for i, v in enumerate(arr)],
                              undefined)[1],
        "find": lambda f: next((v for i, v in enumerate(arr)
                                if js_truthy(call(f, v, i))), undefined),
        "findIndex": lambda f: next((i for i, v in enumerate(arr)
                                     if js_truthy(call(f, v, i))), -1),
        "some": lambda f: any(js_truthy(call(f, v, i))
                              for i, v in enumerate(arr)),
        "every": lambda f: all(js_truthy(call(f, v, i))
                               for i, v in enumerate(arr)),
        "includes": lambda v: any(Interpreter._strict_eq(x, v) for x in arr),
        "indexOf": lambda v: next(
            (i for i, x in enumerate(arr)
             if Interpreter._strict_eq(x, v)), -1),
        "join": lambda sep=",": js_str(sep).join(
            "" if v is undefined or v is None else js_str(v) for v in arr),
        "slice": lambda a=0, b=None: arr[_slice(a, b, len(arr))],
        "concat": lambda *a: arr + [x for chunk in a for x in
                                    (chunk if isinstance(chunk, list)
                                     else [chunk])],
        "reverse": lambda: (arr.reverse(), arr)[1],
        "flat": lambda: [x for v in arr for x in
                         (v if isinstance(v, list) else [v])],
        "sort": lambda f=None: (_js_sort(arr, f), arr)[1],
        "reduce": lambda f, init=undefined: _js_reduce(arr, f, init),
        "splice": lambda start, count=None, *items: _js_splice(
            arr, int(start), count, items),
        "toString": lambda: js_str(arr),
    }
    if name in table:
        return table[name]
    return undefined


def _js_sort(arr, f):
    import functools

    if f is None or f is undefined:
        arr.sort(key=js_str)
    else:
        arr.sort(key=functools.cmp_to_key(
            lambda a, b: (lambda r: -1 if r < 0 else (1 if r > 0 else 0))(
                js_num(f.call([a, b])))))


def _js_reduce(arr, f, init):
    it = iter(enumerate(arr))
    if init is undefined:
        _, acc = next(it)
    else:
        acc = init
    for i, v in it:
        acc = f.call([acc, v, i])
    return acc


def _js_splice(arr, start, count, items):
    if count is None or count is undefined:
        removed = arr[start:]
        arr[start:] = list(items)
    else:
        removed = arr[start:start + int(count)]
        arr[start:start + int(count)] = list(items)
    return removed


def _number_member(x, name):
    table = {
        "toFixed": lambda d=0: f"{x:.{int(d)}f}",
        "toString": lambda: js_str(x),
    }
    return table.get(name, undefined)


def _promise_member(p: JSPromise, name, interp):
    loop = interp.loop
    if name == "then":
        return lambda on_ok=None, on_err=None: _then(p, on_ok, on_err, loop)
    if name == "catch":
        return lambda on_err: _then(p, None, on_err, loop)
    if name == "finally":
        def fin(f):
            # runs on either outcome, passes the settlement through
            def ok(v):
                _call1(f, undefined)
                return v

            def err(e):
                _call1(f, undefined)
                raise JSThrow(e)

            return _then(p, ok, err, loop)
        return fin
    return undefined


# ---------------------------------------------------------------------------
# DOM

_VOID_TAGS = {"br", "hr", "img", "input", "meta", "link"}


class Element:
    def __init__(self, tag: str, doc: "Document"):
        self.tagName = tag.upper()
        self.tag = tag.lower()
        self._doc = doc
        self.attrs: dict[str, str] = {}
        self.children: list[Element] = []
        self.parent: "Element | None" = None
        self._text = ""          # for text nodes (tag == "#text")
        self._listeners: dict[str, list] = {}
        self.dataset = JSObject()
        # live property bag for value/checked/disabled/selected etc.
        self._props: dict[str, Any] = {}

    # -- tree ---------------------------------------------------------------

    def appendChild(self, child: "Element"):
        if child.parent is not None:
            child.parent.children.remove(child)
        child.parent = self
        self.children.append(child)
        return child

    def append(self, *children):
        for c in children:
            if isinstance(c, str):
                c = self._doc.createTextNode(c)
            self.appendChild(c)

    def removeChild(self, child):
        self.children.remove(child)
        child.parent = None
        return child

    def remove(self):
        if self.parent is not None:
            self.parent.removeChild(self)

    # -- text/html ----------------------------------------------------------

    @property
    def textContent(self):
        if self.tag == "#text":
            return self._text
        return "".join(c.textContent for c in self.children)

    @textContent.setter
    def textContent(self, v):
        if self.tag == "#text":
            self._text = js_str(v)
            return
        self.children = []
        if js_str(v):
            t = self._doc.createTextNode(js_str(v))
            self.appendChild(t)

    @property
    def innerHTML(self):
        return "".join(_serialize(c) for c in self.children)

    @innerHTML.setter
    def innerHTML(self, v):
        self.children = []
        for node in _parse_fragment(js_str(v), self._doc):
            self.appendChild(node)

    # -- attributes / properties -------------------------------------------

    def getAttribute(self, name):
        return self.attrs.get(js_str(name), None)

    def setAttribute(self, name, value):
        name = js_str(name)
        self.attrs[name] = js_str(value)
        if name.startswith("data-"):
            self.dataset[_camel(name[5:])] = js_str(value)
        if name == "value":
            self._props.setdefault("value", js_str(value))

    def removeAttribute(self, name):
        self.attrs.pop(js_str(name), None)

    def hasAttribute(self, name):
        return js_str(name) in self.attrs

    @property
    def id(self):
        return self.attrs.get("id", "")

    @property
    def className(self):
        return self.attrs.get("class", "")

    @className.setter
    def className(self, v):
        self.attrs["class"] = js_str(v)

    @property
    def classList(self):
        el = self

        class _CL:
            def add(self, *names):
                cur = el.className.split()
                for nm in names:
                    if nm not in cur:
                        cur.append(js_str(nm))
                el.className = " ".join(cur)

            def remove(self, *names):
                cur = [c for c in el.className.split()
                       if c not in [js_str(n) for n in names]]
                el.className = " ".join(cur)

            def toggle(self, name, force=undefined):
                name = js_str(name)
                has = name in el.className.split()
                want = (not has) if force is undefined else js_truthy(force)
                (self.add if want else self.remove)(name)
                return want

            def contains(self, name):
                return js_str(name) in el.className.split()

        return _CL()

    @property
    def style(self):
        # style as a live property bag persisted across reads
        if "style" not in self._props:
            self._props["style"] = JSObject()
        return self._props["style"]

    # form element properties ------------------------------------------------

    @property
    def value(self):
        if "value" in self._props:
            return self._props["value"]
        if self.tag == "select":
            opts = self.querySelectorAll("option")
            for o in opts:
                if "selected" in o.attrs:
                    return o.value
            return opts[0].value if opts else ""
        if self.tag == "option":
            return self.attrs.get("value", self.textContent)
        if self.tag == "textarea":
            return self.textContent
        return self.attrs.get("value", "")

    @value.setter
    def value(self, v):
        self._props["value"] = js_str(v)

    @property
    def checked(self):
        return self._props.get("checked", "checked" in self.attrs)

    @checked.setter
    def checked(self, v):
        self._props["checked"] = js_truthy(v)

    @property
    def disabled(self):
        return self._props.get("disabled", "disabled" in self.attrs)

    @disabled.setter
    def disabled(self, v):
        self._props["disabled"] = js_truthy(v)

    @property
    def name(self):
        return self.attrs.get("name", "")

    @property
    def type(self):
        return self.attrs.get("type", "")

    @type.setter
    def type(self, v):
        self.attrs["type"] = js_str(v)

    @property
    def href(self):
        return self.attrs.get("href", "")

    @href.setter
    def href(self, v):
        self.attrs["href"] = js_str(v)

    @property
    def src(self):
        return self.attrs.get("src", "")

    @src.setter
    def src(self, v):
        self.attrs["src"] = js_str(v)

    @property
    def options(self):
        return self.querySelectorAll("option")

    @property
    def selectedIndex(self):
        opts = self.options
        val = self.value
        for i, o in enumerate(opts):
            if o.value == val:
                return i
        return -1

    # -- selectors ----------------------------------------------------------

    def _walk(self):
        for c in self.children:
            if c.tag != "#text":
                yield c
                yield from c._walk()

    def querySelectorAll(self, sel):
        out = []
        parts = js_str(sel).strip().split()
        for el in self._walk():
            if _matches(el, parts[-1]):
                # check ancestor chain for descendant combinators
                anc, ok = el.parent, True
                for p in reversed(parts[:-1]):
                    while anc is not None and not _matches(anc, p):
                        anc = anc.parent
                    if anc is None:
                        ok = False
                        break
                    anc = anc.parent
                if ok:
                    out.append(el)
        return out

    def querySelector(self, sel):
        found = self.querySelectorAll(sel)
        return found[0] if found else None

    def getElementById(self, eid):
        eid = js_str(eid)
        for el in self._walk():
            if el.attrs.get("id") == eid:
                return el
        return None

    # -- events -------------------------------------------------------------

    def addEventListener(self, etype, fn, *a):
        self._listeners.setdefault(js_str(etype), []).append(fn)

    def removeEventListener(self, etype, fn, *a):
        ls = self._listeners.get(js_str(etype), [])
        if fn in ls:
            ls.remove(fn)

    def dispatchEvent(self, event: "JSObject"):
        etype = js_str(event.get("type"))
        event.setdefault("target", self)
        # stopPropagation halts the walk BEFORE the next ancestor; the
        # current node's remaining listeners still run (DOM semantics —
        # only stopImmediatePropagation would cut those, unsupported)
        stopped = []
        event["stopPropagation"] = lambda: stopped.append(True)
        node = self
        while node is not None:  # bubble
            for fn in list(node._listeners.get(etype, [])):
                r = (fn.call([event]) if isinstance(fn, JSFunction)
                     else fn(event))
                _raise_if_rejected(r)  # broken async handler = test fails
            if stopped:
                break
            node = node.parent
        return True

    def click(self):
        ev = JSObject({"type": "click", "target": self,
                       "preventDefault": lambda: None})
        self.dispatchEvent(ev)

    def focus(self):
        pass

    def preventDefault(self):  # pragma: no cover - defensive
        pass


def _camel(s: str) -> str:
    parts = s.split("-")
    return parts[0] + "".join(p.title() for p in parts[1:])


def _matches(el: Element, simple: str) -> bool:
    """tag, #id, .class, [attr], :checked — possibly compounded."""
    rest = simple
    while rest:
        m = _re.match(r"^([a-zA-Z][a-zA-Z0-9-]*)", rest)
        if m and rest is simple:
            if el.tag != m.group(1).lower():
                return False
            rest = rest[m.end():]
            continue
        m = _re.match(r"^#([\w-]+)", rest)
        if m:
            if el.attrs.get("id") != m.group(1):
                return False
            rest = rest[m.end():]
            continue
        m = _re.match(r"^\.([\w-]+)", rest)
        if m:
            if m.group(1) not in el.className.split():
                return False
            rest = rest[m.end():]
            continue
        m = _re.match(r"^\[([\w-]+)\]", rest)
        if m:
            if m.group(1) not in el.attrs:
                return False
            rest = rest[m.end():]
            continue
        m = _re.match(r"^:checked", rest)
        if m:
            if not el.checked:
                return False
            rest = rest[m.end():]
            continue
        raise JSError(f"unsupported selector {simple!r}")
    return True


def _serialize(el: Element) -> str:
    if el.tag == "#text":
        return (el._text.replace("&", "&amp;").replace("<", "&lt;")
                .replace(">", "&gt;"))
    attrs = "".join(f' {k}="{v}"' for k, v in el.attrs.items())
    if el.tag in _VOID_TAGS:
        return f"<{el.tag}{attrs}>"
    return f"<{el.tag}{attrs}>{el.innerHTML}</{el.tag}>"


class _FragmentParser(html.parser.HTMLParser):
    def __init__(self, doc):
        super().__init__(convert_charrefs=True)
        self.doc = doc
        self.root = Element("#fragment", doc)
        self.stack = [self.root]

    def handle_starttag(self, tag, attrs):
        el = self.doc.createElement(tag)
        for k, v in attrs:
            el.setAttribute(k, v if v is not None else "")
        self.stack[-1].appendChild(el)
        if tag not in _VOID_TAGS:
            self.stack.append(el)

    def handle_endtag(self, tag):
        for i in range(len(self.stack) - 1, 0, -1):
            if self.stack[i].tag == tag:
                del self.stack[i:]
                break

    def handle_data(self, data):
        if data:
            self.stack[-1].appendChild(self.doc.createTextNode(data))


def _parse_fragment(markup: str, doc) -> list[Element]:
    p = _FragmentParser(doc)
    p.feed(markup)
    return list(p.root.children)


class Document(Element):
    def __init__(self):
        super().__init__("#document", self)
        self._doc = self

    def createElement(self, tag):
        return Element(js_str(tag), self)

    def createTextNode(self, text):
        t = Element("#text", self)
        t._text = js_str(text)
        return t

    @property
    def body(self):
        for el in self._walk():
            if el.tag == "body":
                return el
        return self


class FormData:
    """new FormData(form): input/select/textarea name=value pairs."""

    def __init__(self, form: Element | None = None):
        self._items: list[tuple[str, str]] = []
        if form is not None:
            for el in form.querySelectorAll("input") + \
                    form.querySelectorAll("select") + \
                    form.querySelectorAll("textarea"):
                nm = el.name
                if not nm:
                    continue
                if el.tag == "input" and \
                        el.attrs.get("type") in ("checkbox", "radio"):
                    if not el.checked:
                        continue
                    self._items.append((nm, el.value or "on"))
                else:
                    self._items.append((nm, js_str(el.value)))

    def get(self, name):
        for k, v in self._items:
            if k == js_str(name):
                return v
        return None

    def getAll(self, name):
        return [v for k, v in self._items if k == js_str(name)]

    def entries(self):
        return [[k, v] for k, v in self._items]

    def append(self, k, v):
        self._items.append((js_str(k), js_str(v)))


# ---------------------------------------------------------------------------
# JS <-> Python data conversion for the fetch bridge


def to_js(v):
    if isinstance(v, dict) and not isinstance(v, JSObject):
        return JSObject({k: to_js(x) for k, x in v.items()})
    if isinstance(v, JSObject):
        return JSObject({k: to_js(x) for k, x in v.items()})
    if isinstance(v, list):
        return [to_js(x) for x in v]
    return v


def to_py(v):
    if v is undefined:
        return None
    if isinstance(v, dict):
        return {k: to_py(x) for k, x in v.items()}
    if isinstance(v, list):
        return [to_py(x) for x in v]
    if isinstance(v, float) and v == int(v):
        return int(v)
    return v


# ---------------------------------------------------------------------------
# browser harness


class Browser:
    """Load an HTML page, execute its inline scripts, drive it like a user.

    `router` is a kubeflow_tpu.utils.httpd.Router (the real backend):
    fetch() dispatches HttpReq into it synchronously. Extra routers can
    be mounted under path prefixes with mount() BEFORE load() — the
    dashboard proxies /jupyter/ to JWA the same way the gateway does.
    """

    def __init__(self, router=None):
        self.document = Document()
        self.routers: list[tuple[str, Any]] = []
        if router is not None:
            self.routers.append(("", router))
        self.location = JSObject({"hash": "", "href": "/", "pathname": "/",
                                  "search": ""})
        self.window = Element("#window", self.document)
        self.timers: dict[int, Any] = {}    # id -> interval fn (refire)
        self.timeouts: dict[int, Any] = {}  # id -> one-shot fn (fire once)
        self._timer_seq = 0
        self.console: list[str] = []
        self.requests: list[tuple[str, str]] = []  # (method, path) log
        # headers an auth proxy (gatekeeper/IAP) would inject on every
        # request, e.g. {"kubeflow-userid": "alice@example.com"}
        self.default_headers: dict[str, str] = {}
        self._interp: Interpreter | None = None

    def mount(self, prefix: str, router) -> "Browser":
        self.routers.insert(0, (prefix.rstrip("/"), router))
        return self

    # -- network ------------------------------------------------------------

    def _fetch(self, url, opts=undefined):
        from urllib.parse import parse_qs, urlparse

        from kubeflow_tpu.utils.httpd import HttpReq

        url = js_str(url)
        opts = opts if isinstance(opts, dict) else {}
        method = js_str(opts.get("method", "GET")).upper()
        headers = {k.lower(): v for k, v in self.default_headers.items()}
        headers.update({js_str(k).lower(): js_str(v)
                        for k, v in (opts.get("headers") or {}).items()})
        body = opts.get("body", undefined)
        if isinstance(body, FormData):
            from urllib.parse import urlencode

            raw = urlencode(body._items).encode()
            headers.setdefault("content-type",
                               "application/x-www-form-urlencoded")
        elif body is undefined:
            raw = b""
        else:
            raw = js_str(body).encode()
        parsed = urlparse(url)
        path = parsed.path
        if not path.startswith("/"):  # relative URL: resolve against /
            path = "/" + path
        router = None
        for prefix, r in self.routers:
            if prefix and path.startswith(prefix + "/"):
                router, path = r, path[len(prefix):]
                break
            if not prefix:
                router = r
        if router is None:
            raise JSError(f"no router mounted for {url}")
        self.requests.append((method, path))
        req = HttpReq(method=method, path=path, params={},
                      query=parse_qs(parsed.query), headers=headers, body=raw)
        resp = router.dispatch(req)
        body_bytes = resp.body

        loop = self._interpreter().loop

        def _json():
            try:
                return JSPromise.resolve(
                    to_js(_json_mod_loads(body_bytes.decode() or "null")))
            except Exception:
                return JSPromise.reject(new_error("invalid json"), loop)

        r = JSObject({
            "ok": 200 <= resp.status < 300,
            "status": resp.status,
            "json": _json,
            "text": lambda: JSPromise.resolve(body_bytes.decode()),
        })
        # the request itself ran synchronously above, but the promise
        # settles on a MACROtask (like real network completion): code
        # after the fetch() call — and reactions of earlier fetches —
        # runs first, in queue order
        p = JSPromise.make_pending(loop)
        loop.macrotask(lambda: p.settle_ok(r))
        return p

    # -- page load ----------------------------------------------------------

    def load(self, page_html: str, *, run_scripts: bool = True) -> "Browser":
        self.document.children = []
        for node in _parse_fragment(page_html, self.document):
            self.document.appendChild(node)
        if run_scripts:
            for script in self.document.querySelectorAll("script"):
                src = script.textContent
                if src.strip():
                    self.run(src)
        return self

    def _drain(self) -> "Browser":
        """Run the event loop dry, then fail on any unhandled rejection.
        Called at every harness entry point — the analogue of Selenium's
        'wait for the page to go quiet' between actions."""
        loop = self._interpreter().loop
        loop.drain()
        check_unhandled_rejections(loop)
        return self

    def run(self, js_src: str):
        interp = self._interpreter()
        ast = Parser(tokenize(js_src)).parse_program()
        # top-level scripts share the global env (page scripts do)
        benv = self._genv
        for s in ast[1]:
            if s[0] == "fundecl":
                benv.declare(s[1], interp.make_function(s[2], benv))
        for s in ast[1]:
            interp.exec(s, benv)
        return self._drain()

    def eval(self, js_expr: str):
        """Evaluate an expression in page context (test assertions).
        Trailing tokens are an error — a truncated assertion must never
        pass vacuously."""
        interp = self._interpreter()
        parser = Parser(tokenize(js_expr))
        ast = parser.expression()
        if not parser.at("eof"):
            raise JSError(
                f"trailing tokens after expression: {parser.peek()!r}")
        self._drain()  # pending work settles before the assertion reads
        v = _raise_if_rejected(interp.eval(ast, self._genv))
        if isinstance(v, JSPromise):
            # an expression yielding a promise: settle it for the caller
            interp.loop.drain_until(lambda: v.state != JSPromise.PENDING)
            v = _raise_if_rejected(v).value
        # the expression itself may have created (and orphaned) work
        check_unhandled_rejections(interp.loop)
        return v

    # -- user actions -------------------------------------------------------

    def by_id(self, eid) -> Element:
        el = self.document.getElementById(eid)
        if el is None:
            raise AssertionError(f"no element with id {eid!r}")
        return el

    def click(self, eid):
        self.by_id(eid).click()
        return self._drain()

    def type_into(self, eid, text):
        el = self.by_id(eid)
        el.value = text
        el.dispatchEvent(JSObject({"type": "input", "target": el}))
        el.dispatchEvent(JSObject({"type": "change", "target": el}))
        return self._drain()

    def select(self, eid, value):
        el = self.by_id(eid)
        el.value = value
        el.dispatchEvent(JSObject({"type": "change", "target": el}))
        return self._drain()

    def submit(self, eid):
        el = self.by_id(eid)
        ev = JSObject({"type": "submit", "target": el,
                       "preventDefault": lambda: None})
        el.dispatchEvent(ev)
        return self._drain()

    def set_hash(self, value):
        self.location["hash"] = js_str(value)
        ev = JSObject({"type": "hashchange"})
        for fn in self.window._listeners.get("hashchange", []):
            _raise_if_rejected(
                fn.call([ev]) if isinstance(fn, JSFunction) else fn(ev))
        return self._drain()

    def fire_timers(self):
        """Run every live interval callback once and drain pending
        one-shot timeouts (they never refire — setTimeout semantics).
        Rejected async callbacks raise: a broken timer must fail tests."""
        for fn in list(self.timers.values()):
            _raise_if_rejected(
                fn.call([]) if isinstance(fn, JSFunction) else fn())
        pending, self.timeouts = self.timeouts, {}
        for fn in pending.values():
            _raise_if_rejected(
                fn.call([]) if isinstance(fn, JSFunction) else fn())
        return self._drain()

    def text(self, eid) -> str:
        return self.by_id(eid).textContent

    # -- globals ------------------------------------------------------------

    def _interpreter(self) -> Interpreter:
        if self._interp is not None:
            return self._interp
        g = Env()
        self._genv = g
        interp = Interpreter(g)
        self._interp = interp
        doc = self.document

        def _set_interval(fn, delay=0, *a):
            self._timer_seq += 1
            self.timers[self._timer_seq] = fn
            return self._timer_seq

        def _set_timeout(fn, delay=0, *a):
            self._timer_seq += 1
            self.timeouts[self._timer_seq] = fn
            return self._timer_seq

        def _clear(tid=None):
            # a cancelled timer must NOT fire in fire_timers
            self.timers.pop(tid, None)
            self.timeouts.pop(tid, None)

        def _console_log(*a):
            self.console.append(" ".join(js_str(x) for x in a))

        math = JSObject({
            "max": lambda *a: max(js_num(x) for x in a),
            "min": lambda *a: min(js_num(x) for x in a),
            "round": lambda x: round(js_num(x)),
            "floor": lambda x: int(js_num(x) // 1),
            "abs": lambda x: abs(js_num(x)),
            "random": lambda: 0.42,  # deterministic tests
        })
        obj_ns = JSObject({
            "entries": lambda o: [[k, v] for k, v in o.items()],
            "keys": lambda o: list(o.keys()),
            "values": lambda o: list(o.values()),
            "assign": lambda t, *srcs: (
                [t.update(s) for s in srcs if isinstance(s, dict)], t)[1],
            "fromEntries": lambda pairs: JSObject(
                {js_str(k): v for k, v in pairs}),
        })
        json_ns = JSObject({
            "stringify": lambda v, *a: _json_mod_dumps(to_py(v)),
            "parse": lambda s: to_js(_json_mod_loads(js_str(s))),
        })
        promise_ns = JSObject({
            "resolve": JSPromise.resolve,
            "reject": lambda e: JSPromise.reject(e, interp.loop),
            "all": lambda ps: _promise_all(ps, interp.loop),
        })

        def _error_ctor(message=""):
            return new_error(js_str(message))

        class _URLSearchParams:
            def __init__(self, qs=""):
                from urllib.parse import parse_qs

                self._q = parse_qs(js_str(qs).lstrip("?"),
                                   keep_blank_values=True)

            def get(self, key):
                vals = self._q.get(js_str(key))
                return vals[0] if vals else None

            def getAll(self, key):
                return self._q.get(js_str(key), [])

            def has(self, key):
                return js_str(key) in self._q

        for name, val in {
            "document": doc,
            "window": self.window,
            "location": self.location,
            "history": JSObject({"pushState": lambda *a: undefined,
                                 "replaceState": lambda *a: undefined}),
            "fetch": self._fetch,
            "console": JSObject({"log": _console_log, "warn": _console_log,
                                 "error": _console_log}),
            "JSON": json_ns,
            "Object": obj_ns,
            "Math": math,
            "Promise": promise_ns,
            "Number": lambda v=0: js_num(v),
            "String": lambda v="": js_str(v),
            "Boolean": lambda v=False: js_truthy(v),
            "Array": JSObject({"isArray": lambda v: isinstance(v, list),
                               "from": lambda v: list(v)}),
            "Error": _error_ctor,
            "FormData": FormData,
            "URLSearchParams": _URLSearchParams,
            "parseInt": lambda s, base=10: _parse_int(s, base),
            "parseFloat": lambda s: js_num(s),
            "isNaN": lambda v: js_num(v) != js_num(v),
            "setInterval": _set_interval,
            "setTimeout": _set_timeout,
            "clearInterval": _clear,
            "clearTimeout": _clear,
            "encodeURIComponent": _encode_uri,
            "decodeURIComponent": lambda s: __import__(
                "urllib.parse", fromlist=["unquote"]).unquote(js_str(s)),
            "undefined": undefined,
            "NaN": float("nan"),
            "Infinity": float("inf"),
            "alert": lambda *a: self.console.append(
                "alert: " + " ".join(js_str(x) for x in a)),
            "confirm": lambda *a: True,
        }.items():
            g.declare(name, val)
        # window aliases itself + the globals commonly accessed off it
        self.window.location = self.location
        return interp


def _promise_all(ps, loop: EventLoop) -> JSPromise:
    ps = [JSPromise.resolve(p) for p in ps]
    out = JSPromise.make_pending(loop)
    if not ps:
        out.settle_ok([])
        return out
    results = [undefined] * len(ps)
    left = [len(ps)]
    for i, pr in enumerate(ps):
        pr._handled = True

        def react(i=i, pr=pr):
            if out.state != JSPromise.PENDING:
                return  # already rejected by an earlier settle
            if pr.state == JSPromise.ERR:
                out.settle_err(pr.error)
                return
            results[i] = pr.value
            left[0] -= 1
            if left[0] == 0:
                out.settle_ok(results)

        pr.on_settle(react, loop)
    return out


def _parse_int(s, base=10):
    try:
        return int(js_str(s).strip().split(".")[0], int(base))
    except (ValueError, TypeError):
        return float("nan")


def _encode_uri(s):
    from urllib.parse import quote

    return quote(js_str(s), safe="")


def _json_mod_dumps(v):
    return _json.dumps(v)


def _json_mod_loads(s):
    return _json.loads(s)
