"""Tier-1 static audit: the compiled-program auditor over the REAL
program families, seeded-violation teeth for every rule, the framework
AST lint, and the xprof CI gates.

Layout mirrors paddle_tpu/analysis:
  - TestJaxprWalk / TestBufferAudit / ...: each rule module, on small
    hand-built programs, including a seeded violation per rule (inject
    an f32 matmul under bf16, drop a donation, double a psum, add a
    pure_callback — each must be flagged WITH provenance);
  - TestProgramFamilies: presets.run_cpu_audits over the five real
    families (hybrid train step, PagedEngine prefill/decode/verify/
    page-copy, fused-CE fwd+bwd, fused optimizer write-back, disagg
    migration + router GPT) must be clean — this is the CI invariant
    gate;
  - TestFrameworkLint: the AST lint on a seeded violation tree + the
    allowlist mechanics + the repo itself linting clean;
  - TestXprofGates: tools/xprof_report.py --json/--min-busy-pct exit
    codes over the checked-in fixture trace.

Deep audits (wider TP mesh) ride behind -m slow.
"""

import functools
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.analysis import (buffer_audit, collective_audit,
                                 donation_audit, dtype_audit,
                                 host_sync_audit, jaxpr_walk, presets,
                                 programs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")
sys.path.insert(0, TOOLS)

import framework_lint  # noqa: E402
import xprof_report  # noqa: E402

THIS_FILE = os.path.basename(__file__)


# ---------------------------------------------------------------------------
# walker


class TestJaxprWalk:
    def test_descends_scan_cond_pjit(self):
        def inner(c, x):
            return c + x, jnp.sin(x)

        def f(x):
            c, ys = jax.lax.scan(inner, 0.0, x)
            z = jax.lax.cond(c > 0, jnp.cos, jnp.tanh, c)
            return jax.jit(jnp.exp)(z) + ys.sum()

        jx = jax.make_jaxpr(f)(jnp.arange(4.0))
        prims = {e.primitive.name for e, _ in jaxpr_walk.iter_eqns(jx)}
        # sin lives inside the scan body, cos/tanh inside cond branches,
        # exp inside the nested pjit — the walker must reach all of them
        assert {"sin", "cos", "tanh", "exp"} <= prims

    def test_paths_carry_breadcrumbs(self):
        def f(x):
            return jax.lax.scan(lambda c, v: (c, jnp.sin(v)), 0.0, x)[1]

        jx = jax.make_jaxpr(f)(jnp.arange(3.0))
        paths = [p for e, p in jaxpr_walk.iter_eqns(jx)
                 if e.primitive.name == "sin"]
        assert paths and "scan" in paths[0]

    def test_provenance_names_user_code(self):
        def my_marked_fn(x):
            return jnp.sin(x) * 2

        jx = jax.make_jaxpr(my_marked_fn)(1.0)
        eqn = next(e for e, _ in jaxpr_walk.iter_eqns(jx)
                   if e.primitive.name == "sin")
        prov = jaxpr_walk.provenance(eqn)
        assert THIS_FILE in prov and "my_marked_fn" in prov

    def test_cycle_safe_on_shared_subjaxprs(self):
        body = jax.jit(jnp.sin)

        def f(x):
            return body(x) + body(x * 2)

        jx = jax.make_jaxpr(f)(1.0)
        assert len(list(jaxpr_walk.iter_eqns(jx))) > 0


# ---------------------------------------------------------------------------
# buffer audit


class TestBufferAudit:
    def test_top_intermediates_sorted_with_provenance(self):
        def f(x):
            big = jnp.outer(x, x)          # (64, 64)
            return big.sum() + jnp.sin(x).sum()

        jx = jax.make_jaxpr(f)(jnp.arange(64.0))
        top = buffer_audit.top_intermediates(jx, k=3)
        assert top[0]["shape"] == (64, 64)
        assert top[0]["nbytes"] >= top[-1]["nbytes"]
        assert THIS_FILE in top[0]["provenance"]

    def test_seeded_forbidden_shape_flagged_with_provenance(self):
        def materializes(x, w):
            logits = x @ w                  # (2, 16, 64): the banned class
            return jax.nn.logsumexp(logits, axis=-1).sum()

        jx = jax.make_jaxpr(materializes)(
            jnp.ones((2, 16, 8)), jnp.ones((8, 64)))
        v = buffer_audit.check_forbidden_shape(jx, (2, 16, 64), "seeded",
                                               "full-logits")
        assert v and v[0].rule == "buffer.forbidden-shape"
        assert THIS_FILE in v[0].provenance
        assert "materializes" in v[0].provenance

    def test_seeded_byte_ceiling(self):
        jx = jax.make_jaxpr(lambda x: (x @ x.T).sum())(jnp.ones((32, 8)))
        v = buffer_audit.check_byte_ceiling(jx, 64, "seeded")
        assert v and v[0].rule == "buffer.byte-ceiling"
        assert not buffer_audit.check_byte_ceiling(jx, 10 << 20, "seeded")


# ---------------------------------------------------------------------------
# donation audit


class TestDonationAudit:
    def _trace(self, jitted, *args):
        tr = jitted.trace(*args)
        lo = tr.lower()
        kept = lo._lowering.compile_args.get("kept_var_idx")
        return lo.as_text(), (frozenset(kept) if kept is not None else None)

    def test_seeded_dropped_donation_flagged(self):
        """Satellite teeth: drop a donation from the REAL adamw_update —
        the audit must flag every opt-state leaf as double-buffered."""
        from paddle_tpu.distributed.hybrid_engine import (adamw_init,
                                                          adamw_update)

        params = {"w": jnp.ones((8, 8), jnp.bfloat16),
                  "b": jnp.ones((8,), jnp.bfloat16)}
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        state = adamw_init(params, moments="bf16", master_weights=False)
        step = jax.jit(functools.partial(adamw_update, moments="bf16"))
        text, kept = self._trace(step, params, grads, state)
        v = donation_audit.check_donation(
            text, (params, grads, state), (0, 2), "seeded_no_donate",
            arg_names=("params", "grads", "opt_state"), kept=kept)
        assert v and all(x.rule == "donation.not-aliased" for x in v)
        assert any("opt_state" in x.message for x in v)

    def test_donated_program_is_clean(self):
        from paddle_tpu.distributed.hybrid_engine import (adamw_init,
                                                          adamw_update)

        params = {"w": jnp.ones((8, 8), jnp.bfloat16)}
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        state = adamw_init(params, moments="bf16", master_weights=False)
        step = jax.jit(functools.partial(adamw_update, moments="bf16"),
                       donate_argnums=(0, 2))
        text, kept = self._trace(step, params, grads, state)
        assert donation_audit.check_donation(
            text, (params, grads, state), (0, 2), "seeded_donated",
            kept=kept) == []

    def test_pruned_args_remap_via_kept(self):
        def f(a, b, unused):
            return a + b, b

        j = jax.jit(f, donate_argnums=(0,))
        args = (jnp.ones(4), jnp.ones(4), jnp.ones(7))
        text, kept = self._trace(j, *args)
        assert kept is not None and len(kept) == 2  # 'unused' pruned
        assert donation_audit.check_donation(
            text, args, (0,), "pruned", kept=kept) == []
        # without kept the indices cannot be mapped — must refuse loudly,
        # not guess
        v = donation_audit.check_donation(text, args, (0,), "pruned")
        assert v and v[0].rule == "donation.arg-mismatch"

    def test_spmd_alias_lives_in_compiled_hlo(self):
        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        sm = shard_map(lambda a: a * 2, mesh=mesh, in_specs=(P("mp"),),
                       out_specs=P("mp"))
        j = jax.jit(sm, donate_argnums=(0,))
        tr = j.trace(jax.ShapeDtypeStruct((8,), jnp.float32))
        lo = tr.lower()
        text = lo.as_text()
        # StableHLO only records the request...
        assert "jax.buffer_donor" in text
        assert donation_audit.alias_map(text) == {}
        # ...the resolved alias is in the compiled HLO
        compiled = lo.compile().as_text()
        assert 0 in donation_audit.hlo_alias_map(compiled)
        assert donation_audit.check_donation(
            text, (jnp.ones(8),), (0,), "spmd", compiled_text=compiled
        ) == []

    def test_alias_map_survives_nested_sharding_braces(self):
        sig = ('func.func public @main(%arg0: tensor<4xf32> '
               '{mhlo.sharding = "{replicated}", '
               'tf.aliasing_output = 1 : i32}, '
               '%arg1: tensor<4xf32> {mhlo.sharding = "{replicated}"})')
        assert donation_audit.alias_map(sig) == {0: 1}


# ---------------------------------------------------------------------------
# dtype audit


class TestDtypeAudit:
    def test_seeded_f32_matmul_under_bf16_flagged(self):
        """Satellite teeth: inject an f32 matmul under the bf16 policy —
        flagged with provenance naming this function."""
        def sneaky_f32_matmul(x, w):
            return (x.astype(jnp.float32) @ w.astype(jnp.float32)).sum()

        jx = jax.make_jaxpr(sneaky_f32_matmul)(
            jnp.ones((4, 8), jnp.bfloat16), jnp.ones((8, 4), jnp.bfloat16))
        v = dtype_audit.check_dtype_policy(jx, "seeded", policy="bf16")
        assert v and v[0].rule == "dtype.f32-dot-under-bf16"
        assert "sneaky_f32_matmul" in v[0].provenance
        assert THIS_FILE in v[0].provenance

    def test_bf16_matmul_clean(self):
        jx = jax.make_jaxpr(lambda x, w: x @ w)(
            jnp.ones((4, 8), jnp.bfloat16), jnp.ones((8, 4), jnp.bfloat16))
        assert dtype_audit.check_dtype_policy(jx, "x", policy="bf16") == []

    def test_allowlisted_site_not_flagged(self):
        def blessed_loss_site(x, w):
            return (x.astype(jnp.float32) @ w.astype(jnp.float32)).sum()

        jx = jax.make_jaxpr(blessed_loss_site)(
            jnp.ones((4, 8), jnp.bfloat16), jnp.ones((8, 4), jnp.bfloat16))
        allow = dtype_audit.DEFAULT_F32_DOT_ALLOWLIST + (
            "::blessed_loss_site",)
        assert dtype_audit.check_dtype_policy(
            jx, "x", policy="bf16", allowlist=allow) == []

    def test_f32_policy_is_permissive(self):
        jx = jax.make_jaxpr(lambda x, w: x @ w)(
            jnp.ones((4, 8)), jnp.ones((8, 4)))
        assert dtype_audit.check_dtype_policy(jx, "x", policy="f32") == []


# ---------------------------------------------------------------------------
# host-sync audit


class TestHostSyncAudit:
    def test_seeded_pure_callback_flagged(self):
        """Satellite teeth: add a pure_callback to a step program — the
        audit flags the host round-trip with provenance."""
        def step_with_callback(x):
            y = jax.pure_callback(
                lambda v: np.asarray(v) * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y.sum()

        jx = jax.make_jaxpr(step_with_callback)(jnp.ones(4))
        v = host_sync_audit.check_host_sync(jx, "seeded")
        assert v and v[0].rule == "host-sync.callback-in-step"
        assert "step_with_callback" in v[0].provenance

    def test_seeded_debug_callback_flagged(self):
        def step_with_debug(x):
            jax.debug.callback(lambda v: None, x)
            return x * 2

        jx = jax.make_jaxpr(step_with_debug)(jnp.ones(4))
        assert host_sync_audit.check_host_sync(jx, "seeded")

    def test_callback_inside_scan_found(self):
        def body(c, x):
            jax.debug.callback(lambda v: None, x)
            return c, x

        jx = jax.make_jaxpr(
            lambda x: jax.lax.scan(body, 0.0, x))(jnp.ones(3))
        assert host_sync_audit.check_host_sync(jx, "seeded")

    def test_clean_program(self):
        jx = jax.make_jaxpr(lambda x: jnp.sin(x).sum())(jnp.ones(4))
        assert host_sync_audit.check_host_sync(jx, "x") == []


# ---------------------------------------------------------------------------
# collective audit


def _tp_body(x, w):
    from paddle_tpu.models.generation import _tp_reduce

    return _tp_reduce(x @ w, "mp")


class TestCollectiveAudit:
    def _sharded_jaxpr(self, body):
        from jax import shard_map

        # genuine row-parallel: contraction dim sharded, so the partial
        # products NEED the psum epilogue. check_vma=False matches the
        # engine's shard_map mode (and keeps lax.psum staged as `psum`
        # rather than the vma checker's psum_invariant)
        mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
        f = shard_map(body, mesh=mesh,
                      in_specs=(P(None, "mp"), P("mp", None)),
                      out_specs=P(None), check_vma=False)
        return jax.make_jaxpr(f)(jnp.ones((4, 8)), jnp.ones((8, 4)))

    def test_census_and_fingerprint(self):
        jx = self._sharded_jaxpr(_tp_body)
        census = collective_audit.collective_census(jx)
        assert [c["prim"] for c in census] == ["psum"]
        assert census[0]["axes"] == ("mp",)
        fp = collective_audit.fingerprint(census)
        assert collective_audit.check_collectives(
            jx, "tp", expect_count=1, expect_fingerprint=fp) == []

    def test_seeded_doubled_psum_flagged(self):
        """Satellite teeth: double a psum (helper reduces AND the caller
        reduces again) — count and fingerprint goldens both trip, with
        provenance."""
        from paddle_tpu.models.generation import _tp_reduce

        def doubled(x, w):
            return _tp_reduce(_tp_body(x, w), "mp")

        jx = self._sharded_jaxpr(doubled)
        good_fp = collective_audit.fingerprint(
            collective_audit.collective_census(self._sharded_jaxpr(_tp_body)))
        v = collective_audit.check_collectives(
            jx, "seeded_double_psum", expect_count=1,
            expect_fingerprint=good_fp)
        rules = {x.rule for x in v}
        assert rules == {"collective.count-mismatch",
                         "collective.fingerprint-mismatch"}
        assert all(x.provenance for x in v)

    def test_dropped_psum_changes_fingerprint(self):
        jx = self._sharded_jaxpr(lambda x, w: x @ w)  # forgot the reduce
        v = collective_audit.check_collectives(jx, "seeded_dropped",
                                               expect_count=1)
        assert v and v[0].rule == "collective.count-mismatch"
        assert "found 0" in v[0].message


# ---------------------------------------------------------------------------
# the real program families (the CI invariant gate)


class TestProgramFamilies:
    def test_fused_ce_family_clean(self):
        assert presets.audit_fused_ce() == []

    def test_fused_ce_reference_is_teeth(self):
        _, ref = programs.fused_ce_programs()
        v = buffer_audit.check_forbidden_shape(
            ref.jaxpr, ref.meta["forbidden_shape"], ref.name, "full-logits")
        assert v, "unchunked reference no longer trips the probe — blind"
        # provenance points at the unchunked a @ w in the builder
        assert "programs.py" in v[0].provenance

    def test_train_step_family_clean(self):
        assert presets.audit_train_step() == []

    def test_train_step_audits_real_engine_program(self):
        p = programs.train_step_program()
        # the train step must actually be the hybrid engine's program:
        # donated params+opt aliased, bf16 policy, provenance reaches
        # into hybrid_engine/llama_functional
        top = buffer_audit.top_intermediates(p.jaxpr, k=5)
        files = " ".join(t["provenance"] for t in top)
        assert "llama_functional" in files or "hybrid_engine" in files

    def test_opt_writeback_family_clean(self):
        assert presets.audit_opt_writeback() == []

    def test_serving_family_clean(self):
        assert presets.audit_serving(tp=2) == []

    def test_serving_captured_all_programs(self):
        progs = programs.serving_programs(tp=2)
        assert set(presets.GOLDEN_COLLECTIVES) <= set(progs), \
            "a serving program family stopped being captured"

    def test_serving_collective_goldens_match_formula(self):
        # layers are scanned: the static census is per-body — exactly one
        # psum per row-parallel matmul (wo, w_down), for any layer count
        progs = programs.serving_programs(tp=2)
        for name in ("paged_prefill", "paged_decode", "spec_verify"):
            census = collective_audit.collective_census(progs[name].jaxpr)
            assert [c["prim"] for c in census] == ["psum", "psum"], name
            assert all(c["axes"] == ("mp",) for c in census), name

    def test_latent_serving_family_clean(self):
        assert presets.audit_latent_serving() == []

    def test_latent_serving_captured_all_programs(self):
        progs = programs.latent_serving_programs()
        assert set(presets.LATENT_SERVING) <= set(progs), \
            "a latent serving program stopped being captured"

    @pytest.mark.parametrize("name", presets.LATENT_SERVING)
    def test_latent_pool_is_donated_and_the_step_calls_no_host(self, name):
        p = programs.latent_serving_programs()[name]
        assert p.donated and donation_audit.check_donation(
            p.lowered_text, p.example_args, p.donated, p.name, kept=p.kept,
            compiled_text=p.compiled_text) == []
        assert host_sync_audit.check_host_sync(p.jaxpr, p.name) == []
        assert collective_audit.collective_census(p.jaxpr) == []

    def test_a_window_that_formed_every_rows_logits_would_be_caught(self):
        p = programs.latent_serving_programs()["latent_decode"]
        v = buffer_audit.check_forbidden_shape(
            p.jaxpr, (p.meta["slots"], p.meta["vocab"]), p.name, "logits")
        assert v, "the probe no longer sees the head's [slots, vocab]"

    def test_missing_latent_program_is_reported_not_silent(self,
                                                           monkeypatch):
        real = programs.latent_serving_programs()
        pruned = {k: v for k, v in real.items() if k != "latent_page_copy"}
        monkeypatch.setattr(programs, "latent_serving_programs",
                            lambda: pruned)
        v = presets.audit_latent_serving()
        assert any(x.rule == "audit.program-not-captured"
                   and x.program == "latent_page_copy" for x in v)

    def test_delta_serving_family_clean(self):
        assert presets.audit_delta_serving() == []

    def test_delta_serving_captured_all_programs(self):
        progs = programs.delta_serving_programs()
        assert set(presets.DELTA_SERVING) <= set(progs), \
            "a delta hybrid serving program stopped being captured"

    @pytest.mark.parametrize("name", presets.DELTA_SERVING)
    def test_delta_pools_and_state_are_donated_and_no_host_is_called(
            self, name):
        """The pools AND the tree of per-slot state (matrix states and
        convolution rows) alias their outputs; empty collective golden."""
        p = programs.delta_serving_programs()[name]
        assert p.donated and donation_audit.check_donation(
            p.lowered_text, p.example_args, p.donated, p.name, kept=p.kept,
            compiled_text=p.compiled_text) == []
        assert host_sync_audit.check_host_sync(p.jaxpr, p.name) == []
        assert collective_audit.collective_census(p.jaxpr) == []

    def test_missing_delta_program_is_reported_not_silent(self,
                                                          monkeypatch):
        real = programs.delta_serving_programs()
        pruned = {k: v for k, v in real.items() if k != "delta_state_move"}
        monkeypatch.setattr(programs, "delta_serving_programs",
                            lambda: pruned)
        v = presets.audit_delta_serving()
        assert any(x.rule == "audit.program-not-captured"
                   and x.program == "delta_state_move" for x in v)

    def test_disagg_family_clean(self):
        assert presets.audit_disagg() == []

    def test_disagg_captured_all_programs(self):
        progs = programs.disagg_programs()
        assert set(presets.GOLDEN_DISAGG) <= set(progs), \
            "a disagg program family stopped being captured"

    def test_disagg_migration_is_pure_data_movement(self):
        # a collective creeping into extract/scatter would put a
        # cross-shard hop on every hand-off — the census must stay empty
        progs = programs.disagg_programs()
        for name in ("page_extract", "page_scatter",
                     "page_extract_int8", "page_scatter_int8"):
            assert collective_audit.collective_census(
                progs[name].jaxpr) == [], name

    def test_missing_disagg_program_is_reported_not_silent(self,
                                                           monkeypatch):
        real = programs.disagg_programs()
        pruned = {k: v for k, v in real.items() if k != "page_scatter"}
        monkeypatch.setattr(programs, "disagg_programs", lambda: pruned)
        v = presets.audit_disagg()
        assert any(x.rule == "audit.program-not-captured"
                   and x.program == "page_scatter" for x in v)

    def test_missing_family_is_reported_not_silent(self, monkeypatch):
        real = programs.serving_programs(tp=2)
        pruned = {k: v for k, v in real.items() if k != "spec_verify"}
        monkeypatch.setattr(programs, "serving_programs",
                            lambda tp=2: pruned)
        v = presets.audit_serving(tp=2)
        assert any(x.rule == "audit.program-not-captured"
                   and x.program == "spec_verify" for x in v)

    def test_run_cpu_audits_all_families_clean(self):
        assert presets.run_cpu_audits() == []


@pytest.mark.slow
class TestDeepAudits:
    def test_serving_audit_tp4(self):
        """Wider mesh: the collective structure must be degree-invariant."""
        progs = programs.serving_programs(tp=4, num_heads=4)
        for name, p in progs.items():
            count, fp = presets.GOLDEN_COLLECTIVES[name]
            assert collective_audit.check_collectives(
                p.jaxpr, name, expect_count=count,
                expect_fingerprint=fp) == []


# ---------------------------------------------------------------------------
# framework AST lint


SEEDED_BAD = textwrap.dedent("""\
    import threading
    import time
    import numpy as np
    import jax

    _REG = set()
    _REG_LOCK = threading.Lock()


    def good_register(x):
        with _REG_LOCK:
            _REG.add(x)


    def bad_register(x):
        _REG.add(x)


    def _step_traced(x, n):
        k = int(n)
        t = time.time()
        r = np.random.normal()
        v = x.sum().item()
        return x * k + t + r + v


    def outer(x):
        def inner(y):
            return float(y)
        return jax.jit(inner)(x)


    def host_side(n):
        return int(n)
""")


class TestFrameworkLint:
    @pytest.fixture()
    def seeded_tree(self, tmp_path):
        d = tmp_path / "paddle_tpu" / "serving"
        d.mkdir(parents=True)
        (d / "bad.py").write_text(SEEDED_BAD)
        return tmp_path

    def test_all_rules_fire_on_seeded_tree(self, seeded_tree):
        vs = framework_lint.lint_paths([str(seeded_tree)],
                                       repo_root=str(seeded_tree))
        by_rule = {}
        for v in vs:
            by_rule.setdefault(v.rule, []).append(v)
        assert set(by_rule) == {"JIT01", "JIT02", "JIT03", "LOCK01"}
        assert len(by_rule["JIT01"]) == 3   # int(), .item(), nested float()
        assert any(v.qualname == "outer.inner" for v in by_rule["JIT01"])
        assert by_rule["LOCK01"][0].qualname == "bad_register"
        # every violation carries file:line provenance
        assert all(v.line > 0 and v.path.endswith("bad.py") for v in vs)

    def test_host_side_and_guarded_code_not_flagged(self, seeded_tree):
        vs = framework_lint.lint_paths([str(seeded_tree)],
                                       repo_root=str(seeded_tree))
        quals = {v.qualname for v in vs}
        assert "host_side" not in quals
        assert "good_register" not in quals

    def test_allowlist_requires_justification(self, tmp_path):
        p = tmp_path / "allow.txt"
        p.write_text("JIT01 x.py::f\n")
        entries, errors = framework_lint.load_allowlist(str(p))
        assert not entries and errors and "justification" in errors[0]

    def test_allowlist_suppresses_and_flags_stale(self, seeded_tree):
        vs = framework_lint.lint_paths([str(seeded_tree)],
                                       repo_root=str(seeded_tree))
        lock = next(v for v in vs if v.rule == "LOCK01")
        entries = {lock.key: "single-threaded test scaffolding",
                   "JIT02 ghost.py::nowhere": "stale"}
        kept, stale = framework_lint.apply_allowlist(vs, entries)
        assert lock not in kept
        assert len(stale) == 1 and "ghost.py" in stale[0]

    def test_repo_lints_clean(self):
        vs = framework_lint.lint_paths(
            [os.path.join(REPO, "paddle_tpu"), TOOLS], repo_root=REPO)
        entries, errors = framework_lint.load_allowlist(
            os.path.join(TOOLS, "lint_allowlist.txt"))
        assert not errors
        kept, stale = framework_lint.apply_allowlist(vs, entries)
        assert kept == [] and stale == [], \
            "\n".join(str(v) for v in kept) + "\n".join(stale)

    def test_repo_traced_functions_are_recognized(self):
        """Guard against the lint going blind: the repo's *_traced /
        jitted functions must be detected as traced."""
        import ast

        path = os.path.join(REPO, "paddle_tpu", "serving", "spec_decode.py")
        idx = framework_lint._ModuleIndex()
        idx.visit(ast.parse(open(path).read()))
        framework_lint._mark_traced(idx)
        traced = {i.node.name for i in idx.fns.values() if i.traced}
        assert "_paged_verify_traced" in traced


class TestLintEntry:
    def test_cli_ast_only_green(self):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "lint.py"), "--ast-only"],
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "framework_lint: clean" in r.stdout

    def test_program_audit_entry_in_process(self):
        # same entry tools/lint.py runs; program builds are memoized so
        # this shares the families the tests above already traced
        import importlib

        lint = importlib.import_module("lint")
        assert lint.run_program_audit() == 0


# ---------------------------------------------------------------------------
# xprof CI gates


class TestXprofGates:
    FIXTURE = os.path.join(REPO, "tests", "fixtures", "xprof_trace.json")

    def _report(self):
        events = xprof_report.load_events(self.FIXTURE)
        return xprof_report.build_report(events)

    def test_gates_pass_within_thresholds(self):
        rep = self._report()
        assert xprof_report.check_gates(rep, min_busy_pct=90,
                                        max_non_matmul_pct=20,
                                        min_overlap_pct=70) == []

    def test_gate_failures_name_the_metric(self):
        rep = self._report()
        fails = xprof_report.check_gates(rep, min_busy_pct=99,
                                         max_non_matmul_pct=5,
                                         min_overlap_pct=99)
        assert len(fails) == 3
        assert any("device-busy" in f for f in fails)
        assert any("non-matmul" in f for f in fails)
        assert any("overlap" in f for f in fails)

    def test_cli_exit_codes(self):
        ok = xprof_report.main([self.FIXTURE, "--min-busy-pct", "90"])
        assert ok == 0
        bad = xprof_report.main([self.FIXTURE, "--min-busy-pct", "99.9"])
        assert bad == 2

    def test_json_stdout_machine_readable(self, capsys):
        rc = xprof_report.main([self.FIXTURE, "--json", "-"])
        assert rc == 0
        out = capsys.readouterr().out
        rep = json.loads(out)
        assert "device_busy_pct" in rep and "top_non_matmul" in rep
