"""tpu_lint static-analysis framework: positive/negative fixture pairs per
AST rule, jaxpr-level audits against toy jits, suppression machinery, and the
repo-clean assertion (ref: the reference repo's `tools/` CI-check layer —
op-registry audits / API guards; ours prove the serving engine's
dispatch/sync discipline instead)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import run_ast_checks
from paddle_tpu.analysis.jaxpr_checks import audit_jaxpr, run_jaxpr_checks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_snippet(tmp_path, code, rule=None, registry=None):
    """Write `code` to a fixture file, lint it, return findings (all, or only
    the given rule's)."""
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(code))
    fs = run_ast_checks([str(p)], registry=registry)
    if rule is not None:
        fs = [f for f in fs if f.rule == rule]
    return fs


class _RegistryStub:
    """Registry where every site is declared (TPL002 negative fixture)."""
    class _Entry:
        qualname = ""

    def lookup(self, path, qualname):
        return self._Entry()

    def for_path(self, path):
        return []


# ---------------------------------------------------------------------------
# TPL001 — host sync in step()-reachable code
# ---------------------------------------------------------------------------

def test_tpl001_flags_scalarize_in_hot_loop(tmp_path):
    fs = lint_snippet(tmp_path, """
        class Engine:
            def step(self):
                logits = self._decode_fn(1)
                return int(logits)              # scalar sync on device value
    """, rule="TPL001")
    assert len(fs) == 1 and "int" in fs[0].message


def test_tpl001_implicit_bool_flagged(tmp_path):
    fs = lint_snippet(tmp_path, """
        class Engine:
            def step(self):
                flag = self._decode_fn(1)
                if flag:                        # hidden blocking bool()
                    return 1
    """, rule="TPL001")
    assert len(fs) == 1 and "bool" in fs[0].message


def test_tpl001_silent_on_laundered_fetch(tmp_path):
    # int() over an np.asarray result is host work, not a second sync
    fs = lint_snippet(tmp_path, """
        import numpy as np

        class Engine:
            def step(self):
                logits = self._decode_fn(1)
                with self._span("engine.sample.sync"):
                    logits = np.asarray(logits)
                return int(logits[0])
    """, rule="TPL001")
    assert fs == []


def test_tpl001_silent_outside_hot_path(tmp_path):
    fs = lint_snippet(tmp_path, """
        class Engine:
            def debug_dump(self):               # not step()-reachable
                return int(self._decode_fn(1))
    """, rule="TPL001")
    assert fs == []


# ---------------------------------------------------------------------------
# TPL002 — unregistered jit/shard_map site + stale registry entries
# ---------------------------------------------------------------------------

def test_tpl002_flags_unregistered_jit_site(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def build():
            return jax.jit(lambda x: x + 1)
    """, rule="TPL002")
    assert len(fs) == 1 and "not declared" in fs[0].message


def test_tpl002_silent_when_registered(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def build():
            return jax.jit(lambda x: x + 1)
    """, rule="TPL002", registry=_RegistryStub())
    assert fs == []


def test_tpl002_flags_decorator_jit_sites(tmp_path):
    """@jax.jit / @functools.partial(jax.jit, ...) mint programs exactly like
    call-style sites — both registration (TPL002) and donation (TPL003) must
    see them."""
    code = """
        import functools
        import jax

        @jax.jit
        def step_a(pool, x):
            return pool, x

        @functools.partial(jax.jit, donate_argnums=(0,))
        def step_b(pool, x):
            return pool, x
    """
    t2 = lint_snippet(tmp_path, code, rule="TPL002")
    assert len(t2) == 2                  # both decorators are program sources
    t3 = lint_snippet(tmp_path, code, rule="TPL003", registry=_RegistryStub())
    assert len(t3) == 1 and "step_a" in t3[0].message   # only the undonated


def test_tpl002_flags_orphaned_registry_entry(tmp_path):
    """A registry entry whose FILE was deleted/renamed must be flagged even
    though no per-file pass ever visits it."""
    class _Entry:
        path = str(tmp_path / "deleted_module.py")
        qualname = "gone"

    class _Reg:
        PROGRAM_SOURCES = (_Entry(),)

        def lookup(self, path, qualname):
            return None

        def for_path(self, path):
            return []

    (tmp_path / "present.py").write_text("def f():\n    return 1\n")
    fs = run_ast_checks([str(tmp_path)], registry=_Reg())
    assert any(f.rule == "TPL002" and "no longer exists" in f.message
               for f in fs)
    # root spelled through a '.' segment covers the same entries (absolute
    # containment, not relpath string prefixes)
    fs = run_ast_checks([os.path.join(str(tmp_path), ".")], registry=_Reg())
    assert any(f.rule == "TPL002" and "no longer exists" in f.message
               for f in fs)


def test_tpl002_repo_registry_has_no_stale_entries():
    # every declared source must still have a jit site behind it
    fs = [f for f in run_ast_checks([os.path.join(REPO, "paddle_tpu")])
          if f.rule == "TPL002"]
    assert [f for f in fs if not f.suppressed] == [], \
        [f.format() for f in fs]


# ---------------------------------------------------------------------------
# TPL003 — missing donation on large persistent buffers
# ---------------------------------------------------------------------------

def test_tpl003_flags_undonated_pool(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def decode(params, pool, tokens):
            return pool, tokens

        fn = jax.jit(decode)
    """, rule="TPL003", registry=_RegistryStub())
    assert len(fs) == 1 and "donate_argnums" in fs[0].message


def test_tpl003_silent_with_donation(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def decode(params, pool, tokens):
            return pool, tokens

        fn = jax.jit(decode, donate_argnums=(1,))
    """, rule="TPL003", registry=_RegistryStub())
    assert fs == []


# ---------------------------------------------------------------------------
# TPL004 — Python branch on a traced value
# ---------------------------------------------------------------------------

def test_tpl004_flags_value_branch(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def body(x):
            if x > 0:                   # traced: compiles one program per value
                return x
            return -x

        fn = jax.jit(body)
    """, rule="TPL004", registry=_RegistryStub())
    assert len(fs) == 1 and "`x`" in fs[0].message


def test_tpl004_silent_on_static_tests(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def body(x, y):
            if x.shape[0] > 2:          # shapes are static under tracing
                x = x[:2]
            if y is None:
                return x
            if len(x) > 4:
                return x + y
            return x - y

        fn = jax.jit(body)
    """, rule="TPL004", registry=_RegistryStub())
    assert fs == []


# ---------------------------------------------------------------------------
# TPL005 — blocking fetch outside a RecordEvent span
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fetch", ["np.asarray(out)",
                                   "jax.block_until_ready(out)"])
def test_tpl005_flags_unspanned_fetch(tmp_path, fetch):
    fs = lint_snippet(tmp_path, f"""
        import jax
        import numpy as np

        class Engine:
            def step(self):
                out = self._decode_fn(1)
                return {fetch}                  # untimed blocking fetch/wait
    """, rule="TPL005")
    assert len(fs) == 1 and "RecordEvent" in fs[0].message


@pytest.mark.parametrize("fetch", ["np.asarray(out)",
                                   "jax.block_until_ready(out)"])
def test_tpl005_silent_inside_span(tmp_path, fetch):
    fs = lint_snippet(tmp_path, f"""
        import jax
        import numpy as np

        class Engine:
            def step(self):
                out = self._decode_fn(1)
                with self._span("engine.swap.d2h.ready"):
                    return {fetch}
    """, rule="TPL005")
    assert fs == []


# ---------------------------------------------------------------------------
# TPL006 — broad except around device code
# ---------------------------------------------------------------------------

def test_tpl006_flags_broad_except(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def probe():
            try:
                return jax.devices()
            except Exception:
                return []
    """, rule="TPL006")
    assert len(fs) == 1 and "narrow" in fs[0].message


def test_tpl006_silent_on_narrow_except(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def probe():
            try:
                return jax.devices()
            except RuntimeError:
                return []
    """, rule="TPL006")
    assert fs == []


# ---------------------------------------------------------------------------
# TPL007 — page-state mutation with a double-buffered dispatch in flight
# ---------------------------------------------------------------------------

def test_tpl007_flags_mutation_before_harvest(tmp_path):
    fs = lint_snippet(tmp_path, """
        class Engine:
            def _dispatch(self):
                self._inflight = {"out": 1}     # double-buffer publication

            def _harvest(self, finished):
                self._inflight = None

            def abort(self, rid):
                self.cache.release(rid)         # in-flight batch not harvested
                return True
    """, rule="TPL007")
    assert len(fs) == 1 and "harvest" in fs[0].message \
        and "Engine.abort" in fs[0].message


def test_tpl007_flags_preempt_before_harvest(tmp_path):
    # the oversubscription PR's hazard shape: a public preempt entry point
    # that releases a victim's pages and hands them to a new owner while the
    # double-buffered batch is still in flight — the in-flight harvest would
    # then apply step-n results to step-n+1 page ownership.  (The real
    # engine's preemption runs inside step(), strictly after the step-top
    # harvest, so it passes by construction.)
    fs = lint_snippet(tmp_path, """
        class Engine:
            def _dispatch(self):
                self._inflight = {"out": 1}

            def _harvest(self, finished):
                self._inflight = None

            def preempt_request(self, slot):
                self.cache.release(slot)        # victim pages freed...
                self.cache.allocate(slot, 8)    # ...and reassigned, unharvested
    """, rule="TPL007")
    assert len(fs) == 1 and "Engine.preempt_request" in fs[0].message


def test_tpl007_silent_when_harvested_first(tmp_path):
    # the exact shape LLMEngine.abort/step use: harvest (or a guarded
    # harvest) strictly before the first page-state mutation, including
    # mutations reached through a callee (step -> _admit)
    fs = lint_snippet(tmp_path, """
        class Engine:
            def _dispatch(self):
                self._inflight = {"out": 1}

            def _harvest(self, finished):
                self._inflight = None

            def _admit(self):
                row = self.cache.allocate_prefixed(0, 4, None)

            def abort(self, rid):
                if self._inflight is not None:
                    self._harvest([])
                self.cache.release(rid)
                return True

            def step(self):
                self._harvest([])
                self._admit()
    """, rule="TPL007")
    assert fs == []


def test_tpl007_silent_without_double_buffering(tmp_path):
    # no `_inflight` publication = no in-flight batch to corrupt: a
    # synchronous engine may mutate page state freely
    fs = lint_snippet(tmp_path, """
        class Engine:
            def abort(self, rid):
                self.cache.release(rid)
                return True
    """, rule="TPL007")
    assert fs == []


# ---------------------------------------------------------------------------
# suppression machinery
# ---------------------------------------------------------------------------

def test_suppression_with_reason_silences_and_is_recorded(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def probe():
            try:
                return jax.devices()
            # tpu-lint: disable=TPL006 -- probe is best-effort by design
            except Exception:
                return []
    """)
    t6 = [f for f in fs if f.rule == "TPL006"]
    assert len(t6) == 1 and t6[0].suppressed
    assert t6[0].reason == "probe is best-effort by design"
    assert [f for f in fs if f.rule == "LINT000"] == []


def test_suppression_without_reason_is_lint000_and_ignored(tmp_path):
    fs = lint_snippet(tmp_path, """
        import jax

        def probe():
            try:
                return jax.devices()
            # tpu-lint: disable=TPL006
            except Exception:
                return []
    """)
    assert any(f.rule == "LINT000" for f in fs)
    t6 = [f for f in fs if f.rule == "TPL006"]
    assert len(t6) == 1 and not t6[0].suppressed   # disable had no effect


def test_suppression_syntax_inside_docstring_is_inert(tmp_path):
    """Documentation that QUOTES the disable syntax (a docstring, a string
    literal) must not become a live suppression — only real comments count."""
    fs = lint_snippet(tmp_path, '''
        """Docs: suppress with `# tpu-lint: disable-file=TPL006 -- reason`."""
        import jax

        def probe():
            try:
                return jax.devices()
            except Exception:
                return []
    ''')
    t6 = [f for f in fs if f.rule == "TPL006"]
    assert len(t6) == 1 and not t6[0].suppressed


def test_file_wide_suppression(tmp_path):
    fs = lint_snippet(tmp_path, """
        # tpu-lint: disable-file=TPL006 -- generated bindings, audited upstream
        import jax

        def probe():
            try:
                return jax.devices()
            except Exception:
                return []
    """)
    t6 = [f for f in fs if f.rule == "TPL006"]
    assert len(t6) == 1 and t6[0].suppressed


# ---------------------------------------------------------------------------
# jaxpr level
# ---------------------------------------------------------------------------

def test_jxp001_transfer_inside_program():
    bad = jax.jit(lambda x: jax.device_put(x) + 1)
    good = jax.jit(lambda x: x + 1)
    args = (jnp.ones((4,), jnp.float32),)
    assert any(f.rule == "JXP001" for f in audit_jaxpr("bad", bad, args))
    assert audit_jaxpr("good", good, args) == []


def test_jxp002_undonated_declared_buffer():
    """The deliberately non-donated toy jit: a pool-style dict arg declared
    donated must arrive donated in the pjit params."""
    pool = {"k": jnp.zeros((64, 64), jnp.float32)}
    args = (pool, jnp.ones((), jnp.float32))

    def body(pool, x):
        return {k: v + x for k, v in pool.items()}, x * 2

    bad = jax.jit(body)
    fs = audit_jaxpr("bad", bad, args, donate_paths=("arg0",))
    assert any(f.rule == "JXP002" and "NOT donated" in f.message for f in fs)

    good = jax.jit(body, donate_argnums=(0,))
    assert audit_jaxpr("good", good, args, donate_paths=("arg0",)) == []


def test_jxp002_fails_closed_on_unjitted_callable():
    """A declared donation contract on a callable that never produces a pjit
    eqn (not actually jitted) must be reported, not silently skipped."""
    args = (jnp.zeros((8, 8), jnp.float32),)
    fs = audit_jaxpr("bad", lambda pool: pool * 2, args,
                     donate_paths=("arg0",))
    assert any(f.rule == "JXP002" and "cannot be audited" in f.message
               for f in fs)


def test_jxp002_donated_persistent_buffer_flagged():
    args = (jnp.zeros((8, 8), jnp.float32), jnp.ones((), jnp.float32))
    fn = jax.jit(lambda params, x: params * x, donate_argnums=(0,))
    fs = audit_jaxpr("bad", fn, args, keep_paths=("arg0",))
    assert any(f.rule == "JXP002" and "IS donated" in f.message for f in fs)


def test_jxp003_f64_upcast_flagged():
    args = (jnp.ones((4,), jnp.float32),)
    with jax.enable_x64():
        fs = audit_jaxpr("bad", jax.jit(lambda x: x.astype("float64")), args)
    assert any(f.rule == "JXP003" for f in fs)
    assert audit_jaxpr("good", jax.jit(lambda x: x * 2), args) == []


def test_jxp004_sharding_constraint_required_under_mp():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    import jax.sharding as jsh
    mesh = jsh.Mesh(np.array(jax.devices()[:2]), ("mp",))
    repl = jsh.NamedSharding(mesh, jsh.PartitionSpec())
    args = (jnp.ones((4,), jnp.float32),)
    good = jax.jit(
        lambda x: jax.lax.with_sharding_constraint(x + 1, repl))
    bad = jax.jit(lambda x: x + 1)
    assert audit_jaxpr("good", good, args,
                       require_sharding_constraint=True) == []
    fs = audit_jaxpr("bad", bad, args, require_sharding_constraint=True)
    assert any(f.rule == "JXP004" for f in fs)


def test_jxp005_oversized_host_output():
    """JXP005 pos/neg pair: a program returning [B, V] float logits (or any
    output blob over the int budget) is flagged; a token/accept-sized int
    output with the donated pool riding through passes."""
    B, V = 4, 256
    pool = {"k": jnp.zeros((8, 64), jnp.float32)}
    args = (pool, jnp.zeros((B, 5), jnp.int32))

    def bad_body(pool, tokens):
        logits = jnp.ones((B, V), jnp.float32) * tokens[:, :1]
        return logits, {k: v + 1 for k, v in pool.items()}

    fs = audit_jaxpr("bad", jax.jit(bad_body, donate_argnums=(0,)), args,
                     donate_paths=("arg0",), host_output_budget=B * 8)
    assert any(f.rule == "JXP005" and "logits" in f.message for f in fs)
    assert any(f.rule == "JXP005" and "budget" in f.message for f in fs)

    def bf16_body(pool, tokens):
        # bf16 logprobs SMALL enough to fit the element budget: the
        # float-matrix check alone must catch it (TPU serving dtype)
        lp = jnp.ones((B, 5), jnp.bfloat16) * tokens[:, :1].astype(jnp.bfloat16)
        return lp, {k: v + 1 for k, v in pool.items()}

    fs = audit_jaxpr("bad16", jax.jit(bf16_body, donate_argnums=(0,)), args,
                     donate_paths=("arg0",), host_output_budget=B * 8)
    assert any(f.rule == "JXP005" and "logits" in f.message for f in fs)

    def good_body(pool, tokens):
        preds = jnp.argmax(jnp.ones((B, 5, V)) * tokens[..., None], -1)
        return preds.astype(jnp.int32), jnp.zeros((B,), jnp.int32), \
            {k: v + 1 for k, v in pool.items()}

    assert audit_jaxpr("good", jax.jit(good_body, donate_argnums=(0,)), args,
                       donate_paths=("arg0",),
                       host_output_budget=B * 8) == []


def test_serving_executables_jaxpr_clean():
    """Level 2 over the REAL serving set (the fused one-dispatch step with
    its O(B*K)-int host-output budget, plus the chunk and bucketed
    prefills, the COW copy and the swap pair, mp1 + mp2): donation
    declared == donation traced, no embedded transfers, no f64, mp outputs
    pinned, no logits-shaped host output."""
    assert run_jaxpr_checks(include_mp=True) == []


# ---------------------------------------------------------------------------
# repo-clean + CLI
# ---------------------------------------------------------------------------

def test_repo_inference_package_lints_clean():
    fs = run_ast_checks([os.path.join(REPO, "paddle_tpu", "inference")])
    assert [f.format() for f in fs if not f.suppressed] == []


def test_repo_wide_ast_lint_clean():
    fs = run_ast_checks([os.path.join(REPO, "paddle_tpu"),
                         os.path.join(REPO, "tools"),
                         os.path.join(REPO, "bench_serve.py")])
    assert [f.format() for f in fs if not f.suppressed] == []


def test_cli_exits_nonzero_on_fixture_and_zero_on_clean(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "def probe():\n"
                   "    try:\n"
                   "        return jax.devices()\n"
                   "    except Exception:\n"
                   "        return []\n")
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n")
    tool = os.path.join(REPO, "tools", "tpu_lint.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, tool, "--level", "ast", str(bad)],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 1 and "TPL006" in r.stdout
    r = subprocess.run([sys.executable, tool, "--level", "ast", str(clean)],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 0
    # a typo'd path must not report "clean": lint-nothing is a config error
    r = subprocess.run([sys.executable, tool, "--level", "ast",
                        "paddle_tpu/inferenec"],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 2 and "no such path" in r.stderr
    # ...and so is an existing path that yields zero python files
    empty = tmp_path / "empty"
    empty.mkdir()
    r = subprocess.run([sys.executable, tool, "--level", "ast", str(empty)],
                       capture_output=True, text=True, env=env, cwd=REPO)
    assert r.returncode == 2 and "no python files" in r.stderr
