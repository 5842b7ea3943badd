"""paddle_tpu.serving — continuous-batching LLM serving.

    Router / DisaggServer -> PagedEngine (host) -> path (device state and
    programs) -> models/*_functional -> kernels/

  - `Engine` (engine.py): the iteration-level scheduler (Orca-style) over a
    slot-based KV cache, one `max_len` stripe per slot. The simple baseline;
    `Request`, the step phases and the streaming callbacks live here.
  - `PagedEngine` (paged_engine.py): the same scheduler over a PAGED cache,
    the host half only: block tables, `BlockAllocator` (block_manager.py:
    refcounted pages, copy-on-write, radix-tree prefix reuse at token
    granularity, leaf-LRU eviction), admission by worst-case page count,
    CHUNKED PREFILL (`prefill_chunk=`) interleaved with decode steps,
    `preempt` / `resume`. Greedy output is token-for-token sequential
    `generate`'s.
  - the PATH (paths.py: the table and the interface): everything on the
    device is one object a model family, chosen by the type of `args` —
    dense.py for a `LlamaArgs` (the page pools in the model dtype or
    `kv_dtype="int8"`, TENSOR PARALLELISM over `mesh=` with tp.py's
    placement, the disaggregated page mover), family.py for every other
    family: ONE path over a tree of page pools and a tree of per-slot state
    (a recurrent state, snapshots of it; empty where a request is its pages
    alone), which the family's functional module fills
    (`models/family_protocol.py`); routing.py is the host half of what an
    expert stack's steps return beside their tokens.
  - SPECULATIVE DECODING (spec_decode.py): `draft_params=` / `draft_args=`
    propose `spec_tokens` tokens in one traced scan and verify the window in
    one batched paged forward; the block table rolls back to what was
    accepted.
  - per-request sampling (sampler.py): `Request(temperature=, top_p=, top_k=,
    seed=)` as traced per-row vectors; greedy rows stay bit-exact argmax in
    mixed batches and seeds make tokens batch-independent.
  - DISAGGREGATED PREFILL / DECODE (disagg.py): `PrefillWorker` and
    `DecodeWorker` are role-restricted `PagedEngine`s; a finished prefill
    ships as a `KVHandoff` (page contents verbatim) over a `LocalTransport`
    or a `StoreTransport`; `DisaggServer` wires one of each.
  - SLO-AWARE MULTI-MODEL ROUTER (router.py): `Router` fronts named backends
    (`PagedEngine`, `GptEngine`, `BertBackend`) with `slo="interactive" |
    "batch"` classes and preemption of batch slots.

scheduler.py holds the admission queue, length buckets, slot table and page
math; metrics.py the counters, gauges and observations that also back
`inference.Config.enable_profile()`.

    from paddle_tpu.serving import PagedEngine, Request

    eng = PagedEngine(params, args, max_slots=32, max_len=1024,
                      page_size=64, num_pages=256)
    req = eng.submit(Request(prompt_ids, max_new_tokens=64,
                             eos_token_id=2, stream_cb=on_token))
    eng.run_until_idle()          # req.token_ids, req.ttft_s, ...
    print(eng.metrics.summary())
"""

from paddle_tpu.serving.block_manager import (NULL_PAGE, BlockAllocator,
                                              PrefixMatch)
from paddle_tpu.serving.disagg import (DecodeWorker, DisaggServer,
                                       KVHandoff, LocalTransport,
                                       PrefillWorker, StoreTransport)
from paddle_tpu.serving.engine import Engine, Request
from paddle_tpu.serving.metrics import Metrics
from paddle_tpu.serving.paged_engine import PagedEngine
from paddle_tpu.serving.router import (BertBackend, EmbeddingRequest,
                                       GptEngine, Router)
from paddle_tpu.serving.sampler import SlotSampler
from paddle_tpu.serving.scheduler import (AdmissionQueue, SlotTable,
                                          bucket_for, pages_for)
from paddle_tpu.serving.spec_decode import SpecDecoder

__all__ = ["Engine", "PagedEngine", "Request", "Metrics", "BlockAllocator",
           "PrefixMatch", "NULL_PAGE", "AdmissionQueue", "SlotTable",
           "SlotSampler", "SpecDecoder", "bucket_for", "pages_for",
           "PrefillWorker", "DecodeWorker", "DisaggServer", "KVHandoff",
           "LocalTransport", "StoreTransport", "Router", "GptEngine",
           "BertBackend", "EmbeddingRequest"]
