"""The wired-up CPU audit: every rule over every real program family.

`run_cpu_audits()` is the single entry point tier-1 and tools/lint.py
share. It builds the five program families at toy size (fused-CE
fwd+bwd, the hybrid engine's train step, the fused optimizer
write-back, the PagedEngine's captured serving steps, and the
disaggregated-serving migration + router-GPT programs) and applies the
rule suite with the repo's pinned invariants:

  - no [batch, seq, vocab] intermediate anywhere near the loss;
  - per-program byte ceilings on the largest intermediate (backstop for
    shape regressions the forbidden-shape probe doesn't name);
  - donated state (params, opt state, KV page pool) actually aliased in
    the lowered/compiled program;
  - bf16 AMP: f32 dot_generals only at allowlisted loss/norm sites;
  - no host callbacks in any step program;
  - TP serving collectives pinned by count AND fingerprint — every
    row-parallel matmul carries exactly one psum reduce epilogue (the
    decoder layers are scanned, so the static census is per-body: 2
    psums over ('mp',), for any layer count).

GOLDEN fingerprints are regenerated with
`collective_audit.fingerprint(collective_audit.collective_census(j))`
after an INTENTIONAL collective change — say why in the diff.
"""

from __future__ import annotations

from paddle_tpu.analysis import (buffer_audit, collective_audit,
                                 donation_audit, dtype_audit,
                                 host_sync_audit, programs)

__all__ = ["GOLDEN_COLLECTIVES", "GOLDEN_DISAGG", "BYTE_CEILINGS",
           "run_cpu_audits"]

# static collective structure of each serving program: the layer stack
# is a scan, so the census counts the body once — 2 row-parallel psum
# epilogues (wo, w_down) regardless of num_layers; page_copy is pure
# data movement and must stay collective-free
_TP_FP = "a91763b43edf"       # psum@mp;psum@mp
_EMPTY_FP = "da39a3ee5e6b"    # empty census
GOLDEN_COLLECTIVES = {
    "paged_prefill": (2, _TP_FP),
    "paged_decode": (2, _TP_FP),
    "spec_verify": (2, _TP_FP),
    "page_copy": (0, _EMPTY_FP),
    # kv_dtype='int8' family: quantize-at-scatter / dequant-at-gather are
    # elementwise per shard, so the census must be IDENTICAL to the
    # model-dtype pool — and the int8 page copy (codes + scale leaves)
    # stays collective-free
    "paged_prefill_int8": (2, _TP_FP),
    "paged_decode_int8": (2, _TP_FP),
    "page_copy_int8": (0, _EMPTY_FP),
}

# the disaggregated-serving + router family is its OWN golden dict: the
# serving captures above must not silently grow entries when disagg
# programs change (and vice versa). The migration pair is pure data
# movement — a collective creeping into extract/scatter would put a
# cross-shard hop on every hand-off; the GPT stripe programs are
# single-chip (the router's second model family has no TP mesh).
GOLDEN_DISAGG = {
    "page_extract": (0, _EMPTY_FP),
    "page_scatter": (0, _EMPTY_FP),
    "page_extract_int8": (0, _EMPTY_FP),
    "page_scatter_int8": (0, _EMPTY_FP),
    "gpt_prefill": (0, _EMPTY_FP),
    "gpt_decode": (0, _EMPTY_FP),
}

# largest-intermediate ceilings at the toy geometry (measured max plus
# ~40% headroom): a blowup past these means a buffer class that did not
# exist when the budget was pinned
BYTE_CEILINGS = {
    "fused_ce_fwd_bwd": 12 * 1024,
    "hybrid_train_step": 18 * 1024,
    "fused_opt_writeback": 18 * 1024,
    "paged_prefill": 26 * 1024,
    "paged_decode": 26 * 1024,
    "spec_verify": 26 * 1024,
    "page_copy": 26 * 1024,
    # the latent-attention expert family: the largest buffer is the pool
    # itself (108K at the toy size, its rows padded to a lane tile), which
    # every program carries through and none copies
    "latent_prefill": 152 * 1024,
    "latent_decode": 152 * 1024,
    "latent_page_copy": 152 * 1024,
    # the gated delta-rule hybrid family (through the one `FamilyPath`):
    # the largest buffer is a full layer's page pool (9,216 B at the toy
    # size), which every program carries through and none copies; the
    # state's mover tops out at the snapshots' matrix states (8,192 B)
    "delta_prefill": 13 * 1024,
    "delta_decode": 13 * 1024,
    "delta_page_copy": 13 * 1024,
    "delta_state_move": 12 * 1024,
    # int8 pool: the pool buffers shrink 2-4x but the prefill gather
    # dequantizes pages to f32 before attention, so the ceilings stay at
    # the model-dtype budget rather than scaling with the pool
    "paged_prefill_int8": 26 * 1024,
    "paged_decode_int8": 26 * 1024,
    "page_copy_int8": 26 * 1024,
    # disagg migration: extract gathers ONE request's pages (measured 4K
    # model-dtype / 1K int8 codes at toy size); scatter's largest buffer
    # is the destination pool leaf it writes through (18K / 4.5K). The
    # GPT stripe programs top out at the [slots, heads, len, hd] KV
    # stripe (16K).
    "page_extract": 6 * 1024,
    "page_extract_int8": 2 * 1024,
    "page_scatter": 26 * 1024,
    "page_scatter_int8": 7 * 1024,
    "gpt_prefill": 23 * 1024,
    "gpt_decode": 23 * 1024,
}

_TRAIN_ARG_NAMES = ("params", "opt_state", "ids", "labels")
_OPT_ARG_NAMES = ("params", "grads", "opt_state")


def _common(p, out):
    """Rules every program family gets: host-sync ban + byte ceiling."""
    out += host_sync_audit.check_host_sync(p.jaxpr, p.name)
    ceiling = BYTE_CEILINGS.get(p.name)
    if ceiling is not None:
        out += buffer_audit.check_byte_ceiling(p.jaxpr, ceiling, p.name)


def _donation(p, out, arg_names=None):
    out += donation_audit.check_donation(
        p.lowered_text, p.example_args, p.donated, p.name,
        arg_names=arg_names, kept=p.kept, compiled_text=p.compiled_text)


def audit_fused_ce():
    fused, _ = programs.fused_ce_programs()
    out = []
    out += buffer_audit.check_forbidden_shape(
        fused.jaxpr, fused.meta["forbidden_shape"], fused.name,
        "full-logits")
    out += buffer_audit.check_forbidden_carry(
        fused.jaxpr, fused.meta["forbidden_carry"], "float32", fused.name,
        "head-gradient accumulator")
    _common(fused, out)
    return out


def audit_train_step():
    p = programs.train_step_program()
    out = []
    out += buffer_audit.check_forbidden_shape(
        p.jaxpr, p.meta["forbidden_shape"], p.name, "full-logits")
    out += dtype_audit.check_dtype_policy(p.jaxpr, p.name,
                                          policy=p.meta["policy"])
    _donation(p, out, _TRAIN_ARG_NAMES)
    _common(p, out)
    return out


def audit_opt_writeback():
    p = programs.opt_writeback_program()
    out = []
    _donation(p, out, _OPT_ARG_NAMES)
    _common(p, out)
    return out


def audit_serving(tp=2):
    progs = programs.serving_programs(tp=tp)
    out = []
    from paddle_tpu.analysis.base import Violation
    missing = sorted(set(GOLDEN_COLLECTIVES) - set(progs))
    for name in missing:
        # a family that silently stopped being captured is itself a
        # finding — the audit must not go blind without failing
        out.append(Violation(
            rule="audit.program-not-captured", program=name,
            message="serving program was never dispatched/captured — "
                    "scheduler or capture-harness change?"))
    for name, p in sorted(progs.items()):
        count, fp = GOLDEN_COLLECTIVES.get(name, (None, None))
        out += collective_audit.check_collectives(
            p.jaxpr, name, expect_count=count, expect_fingerprint=fp)
        _donation(p, out)
        _common(p, out)
    return out


LATENT_SERVING = ("latent_prefill", "latent_decode", "latent_page_copy")


def audit_latent_serving():
    """The latent-attention expert family's step programs: the pool donated
    and aliased, no collective (the family has no mesh), no host callback
    (the routing counts ride the tokens' output), a byte ceiling,
    and logits for the head's rows alone: the prefill window forms none for
    its other rows, the decode step one `[slots, vocab]` and no second."""
    progs = programs.latent_serving_programs()
    out = []
    from paddle_tpu.analysis.base import Violation
    for name in sorted(set(LATENT_SERVING) - set(progs)):
        out.append(Violation(
            rule="audit.program-not-captured", program=name,
            message="latent serving program was never dispatched/captured "
                    "— scheduler or capture-harness change?"))
    for name, p in sorted(progs.items()):
        out += collective_audit.check_collectives(
            p.jaxpr, name, expect_count=0, expect_fingerprint=_EMPTY_FP)
        _donation(p, out)
        _common(p, out)
    if "latent_prefill" in progs:
        p = progs["latent_prefill"]
        out += buffer_audit.check_forbidden_shape(
            p.jaxpr, (p.meta["bucket"], p.meta["vocab"]), p.name,
            "logits of a whole window")
    if "latent_decode" in progs:
        p = progs["latent_decode"]
        rows = [eqn for _, aval, eqn, _ in buffer_audit.intermediates(p.jaxpr)
                if tuple(aval.shape) == (p.meta["slots"], p.meta["vocab"])]
        if len(rows) > 2:     # the head's matmul and its cast to float32
            out.append(Violation(
                rule="buffer.forbidden-shape", program=p.name,
                message=f"{len(rows)} [slots, vocab] intermediates in the "
                        "decode step: the head makes one (and casts it)"))
    return out


DELTA_SERVING = ("delta_prefill", "delta_decode", "delta_page_copy",
                 "delta_state_move")


def audit_delta_serving():
    """The gated delta-rule hybrid family's programs through the one
    `FamilyPath`: the pools and the tree of per-slot state donated and
    aliased, no collective (the family has no mesh), no host callback, a
    byte ceiling, and logits for the head's rows alone."""
    progs = programs.delta_serving_programs()
    out = []
    from paddle_tpu.analysis.base import Violation
    for name in sorted(set(DELTA_SERVING) - set(progs)):
        out.append(Violation(
            rule="audit.program-not-captured", program=name,
            message="delta hybrid serving program was never dispatched/"
                    "captured — scheduler or capture-harness change?"))
    for name, p in sorted(progs.items()):
        out += collective_audit.check_collectives(
            p.jaxpr, name, expect_count=0, expect_fingerprint=_EMPTY_FP)
        _donation(p, out)
        _common(p, out)
    if "delta_prefill" in progs:
        p = progs["delta_prefill"]
        out += buffer_audit.check_forbidden_shape(
            p.jaxpr, (p.meta["bucket"], p.meta["vocab"]), p.name,
            "logits of a whole window")
    return out


def audit_disagg():
    """The disaggregated-serving family: KV-page migration programs
    (model-dtype + int8 pools) and the router's GPT stripe programs —
    census pinned by GOLDEN_DISAGG, scatter/stripe donation aliased,
    host-sync ban + byte ceilings throughout."""
    progs = programs.disagg_programs()
    out = []
    from paddle_tpu.analysis.base import Violation
    for name in sorted(set(GOLDEN_DISAGG) - set(progs)):
        out.append(Violation(
            rule="audit.program-not-captured", program=name,
            message="disagg program was never dispatched/captured — "
                    "scheduler or capture-harness change?"))
    for name, p in sorted(progs.items()):
        count, fp = GOLDEN_DISAGG.get(name, (None, None))
        out += collective_audit.check_collectives(
            p.jaxpr, name, expect_count=count, expect_fingerprint=fp)
        _donation(p, out)
        _common(p, out)
    return out


def run_cpu_audits(families=("fused_ce", "train_step", "opt_writeback",
                             "serving", "latent_serving", "delta_serving",
                             "disagg")):
    """Run every audit family; returns the full list of Violations
    (empty = the repo's compiled programs uphold every invariant)."""
    runners = {
        "fused_ce": audit_fused_ce,
        "train_step": audit_train_step,
        "opt_writeback": audit_opt_writeback,
        "serving": audit_serving,
        "latent_serving": audit_latent_serving,
        "delta_serving": audit_delta_serving,
        "disagg": audit_disagg,
    }
    out = []
    for fam in families:
        out += runners[fam]()
    return out
