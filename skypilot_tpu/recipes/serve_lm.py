"""In-framework LM inference server: the payload of serve replicas.

Thin CLI over `skypilot_tpu.inference` (runtime construction in
inference/runtime.py, HTTP + SSE streaming in inference/http_server.py,
OpenAI shims in inference/openai_compat.py). A JetStream-shaped HTTP
server: GET / (readiness), POST /generate {"tokens": [[...]],
"max_new_tokens": N, "temperature": t, "top_k": k, "top_p": p} →
{"tokens": [[...]]}, plus /generate_text and OpenAI-compatible
/v1/completions + /v1/chat/completions with SSE streaming
(`"stream": true`) and n>1, plus observability endpoints: GET /stats
(JSON rolling-window snapshot) and GET /metrics (Prometheus text —
engine internals + request-path histograms; metric catalog in
docs/guides.md). Listens on SKYPILOT_SERVE_PORT (injected
by the serve controller). Two engines:

  - default: one jitted fixed-shape generate fn per batch bucket
    (models/generate.py) — simplest, one request at a time (streaming
    requests ride a small lazily-built slot engine);
  - --continuous-batching: the slot-based engine
    (models/batching.py) — concurrent requests share the decode
    loop, joining and leaving without draining the batch (the
    throughput mode under ragged request lengths).

  stpu serve up -y -n llama task.yaml   # run: python -m
      skypilot_tpu.recipes.serve_lm --model llama-tiny
"""
from __future__ import annotations

import argparse
import os


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument('--model', default='llama-tiny',
                        help='registry name (recipes/train_lm.py). A '
                             'model with state-space layers '
                             '(nemotron3-super-l11-ep4, '
                             'nemotron-h-tiny) keeps recurrent state '
                             'by SLOT beside its K/V pages (/stats '
                             'state_pool): a cached, spilled or '
                             'exported page holds no state to resume '
                             'from, so prefix caching is off and '
                             '--kv-spill-bytes/--kv-cold-dir, --role/'
                             '--decode-peers, --stages, --tensor > 1, '
                             '--speculative, --decode-chunk > 1, '
                             '--kv-dtype int8 and the one-shot engine '
                             'are refused by name (docs/guides.md '
                             '"State by slot")')
    parser.add_argument('--hf', default=None, metavar='DIR',
                        help='serve a HuggingFace checkpoint from a '
                             'local directory (e.g. the target of an '
                             'hf:// storage COPY): weights are '
                             'converted in-process '
                             '(models/hf_import.py) and --model is '
                             'ignored; if tokenizer files are present, '
                             'POST /generate_text serves text in/out')
    parser.add_argument('--ckpt-dir', default=None,
                        help='orbax checkpoint to load weights from')
    parser.add_argument('--max-total-len', type=int, default=256)
    parser.add_argument('--continuous-batching', action='store_true',
                        help='slot-based engine: concurrent requests '
                             'share the decode loop')
    parser.add_argument('--num-slots', type=int, default=8)
    parser.add_argument('--decode-chunk', type=int, default=1,
                        metavar='N',
                        help='continuous engine: N decode steps per '
                             'jitted dispatch (lax.scan) — outputs '
                             'identical to step-by-step; amortizes '
                             'per-dispatch host overhead (the serving '
                             'analog of the trainer multi-step). '
                             'Trade-off: up to N-1 wasted steps per '
                             'finishing request, admission at chunk '
                             'boundaries. Exclusive with --speculative')
    parser.add_argument('--prefill-chunk', type=int, default=256,
                        metavar='C',
                        help='continuous engine: chunked prefill — '
                             'admitted prompts prefill in C-token '
                             'chunks interleaved with decode steps '
                             '(one compiled shape instead of a log2 '
                             'bucket ladder), so one long prompt '
                             'cannot stall every active decode slot. '
                             'A multiple of the KV page size (16) '
                             'lets chunks go into the pool by pages. '
                             '0 = whole-prompt prefill (the legacy '
                             'synchronous path)')
    parser.add_argument('--prefill-budget', type=int, default=0,
                        metavar='T',
                        help='max prefill tokens run per scheduler '
                             'iteration (chunked prefill only). '
                             'Default 0 = one chunk per iteration — '
                             'maximal decode interleaving; raise it '
                             'to favor time-to-first-token over '
                             'inter-token latency')
    parser.add_argument('--no-pipeline-decode', action='store_true',
                        help='disable one-step host/device decode '
                             'pipelining (dispatch round N+1 before '
                             'committing round N). On by default for '
                             'the plain decode loop; greedy outputs '
                             'are identical either way')
    parser.add_argument('--speculative', type=int, default=0,
                        metavar='K',
                        help='prompt-lookup speculative decoding with K '
                             'drafted tokens per step. One-shot engine: '
                             'greedy requests, exact greedy outputs. '
                             'Continuous batching: every slot rides '
                             'verify chunks (greedy exact; sampled '
                             'stays unbiased via match-acceptance)')
    parser.add_argument('--port', type=int,
                        default=int(os.environ.get('SKYPILOT_SERVE_PORT',
                                                   8000)))
    parser.add_argument('--zone', default='',
                        help='placement zone label (spot decode '
                             'pools): scoped into the preemption '
                             'watcher\'s serve.preempt_notice fault '
                             'point, echoed in /stats — a zone-'
                             'scoped storm plan preempts only the '
                             'replicas carrying the zone')
    parser.add_argument('--tensor', type=int, default=1,
                        help='tensor-parallel serving over N devices: '
                             'params shard per the training rules '
                             '(heads/mlp/vocab over the tensor axis) '
                             'and XLA propagates the sharding through '
                             'every serving fn — models bigger than '
                             'one chip serve across the slice. The '
                             'KV page pool shards its kv-heads axis '
                             'too (when N divides the head count), '
                             'so N chips hold ~Nx the pages at fixed '
                             'per-chip --kv-pool-bytes')
    parser.add_argument('--stages', type=int, default=1,
                        help='pipeline-parallel serving over S stages: '
                             'the layer stack splits into S contiguous '
                             'ranges, each placed on its own tensor '
                             'submesh of a (stage, tensor) mesh — '
                             'total chips = S x --tensor. Prefill '
                             'streams chunk microbatches through the '
                             'stage chain; decode keeps S slot groups '
                             'in flight so every stage works each '
                             'step. Each stage\'s KV pool holds only '
                             'its own layers\' pages, so the pool '
                             'scales ~S x --tensor ways at fixed '
                             'per-chip --kv-pool-bytes. Needs '
                             '--continuous-batching; does not compose '
                             'with --weight-dtype int8 or '
                             '--decode-chunk > 1')
    parser.add_argument('--adapter-dir', default=None, metavar='DIR',
                        help='multi-LoRA serving: a local or gs:// '
                             'directory of adapter artifacts '
                             '(<name>/adapter_config.json + weights, '
                             'the train_lm --lora output). The '
                             '`model` field on /v1/* and /generate* '
                             'selects an adapter by name; adapters '
                             'hot-load on first use and LRU-evict '
                             'under the --max-adapters device budget')
    parser.add_argument('--max-adapters', type=int, default=8,
                        metavar='N',
                        help='device-resident adapter slots in the '
                             'stacked LoRA store (memory = N x '
                             'per-adapter factor bytes; see '
                             'docs/guides.md "Multi-LoRA serving")')
    parser.add_argument('--max-lora-rank', type=int, default=0,
                        metavar='R',
                        help='store rank ceiling (smaller-rank '
                             'adapters zero-pad). 0 = the max rank '
                             'seen in --adapter-dir at startup; set '
                             'it explicitly if bigger-rank adapters '
                             'will be hot-dropped in later')
    parser.add_argument('--no-prefix-caching', action='store_true',
                        help='disable shared-prefix KV page reuse '
                             '(vLLM-style APC; on by default with the '
                             'paged cache — repeated system prompts '
                             'skip recomputation and share pool pages)')
    parser.add_argument('--kv-dtype', choices=['bf16', 'int8'],
                        default='bf16',
                        help='KV page-pool storage format. int8 '
                             'stores quantized pages + per-page-slot '
                             'f32 scales (quantize on write, dequant '
                             'inside attention): ~2x decode slots and '
                             'prefix-cache residency per HBM byte, '
                             'quality pinned by the logprob-tolerance '
                             'contract (docs/guides.md "Quantized '
                             'serving"). Needs --continuous-batching')
    parser.add_argument('--kv-pool-bytes', type=int, default=0,
                        metavar='B',
                        help='size the KV page pool by PER-CHIP '
                             'device bytes instead of the model '
                             'default page count: kv_total_pages = '
                             'B // per-page-per-chip bytes under '
                             '--kv-dtype and --tensor, so a bf16 vs '
                             'int8 A/B at the same B spends the same '
                             'HBM (int8 buys ~2x the pages) and an '
                             'N-chip mesh with the kv-heads axis '
                             'sharded holds ~Nx the TOTAL pages at '
                             'the same per-chip spend. 0 = '
                             'model-default page count')
    parser.add_argument('--weight-dtype', choices=['bf16', 'int8'],
                        default='bf16',
                        help='serving storage for the projection '
                             'weights (wq/wk/wv/wo, w_gate/w_up/'
                             'w_down). int8 = per-output-channel '
                             'symmetric quantization, dequantized on '
                             'read inside the jitted fns — halves '
                             'weight-streaming HBM bandwidth; '
                             'embeddings/norms/head stay bf16. '
                             'Composes with --tensor (scales shard '
                             'with their channel) and LoRA (deltas '
                             'ride the dequantized base)')
    parser.add_argument('--param-dtype', choices=['bf16', 'f32'],
                        default='bf16',
                        help='on-device dtype for --hf weights. bf16 '
                             '(default) halves HBM vs f32; compute '
                             'already runs in bf16 either way. Models '
                             'bigger than one chip serve with '
                             '--tensor N (sharded across the slice). '
                             'f32 is for CPU parity runs')
    parser.add_argument('--role', choices=['', 'prefill', 'decode'],
                        default='',
                        help='disaggregated serving role. "prefill": '
                             'this replica prefills prompts and hands '
                             'the KV page chain off to a decode peer '
                             '(POST /kv/import) instead of decoding '
                             'locally, falling back to local serving '
                             'when the transfer fails; "decode": '
                             'label only (pool membership for the '
                             'fleet controller / LB). Default: '
                             'unified replica. prefill needs '
                             '--continuous-batching')
    parser.add_argument('--decode-peers', default=None,
                        metavar='HOST:PORT,...',
                        help='static decode pool for --role prefill '
                             '(the fleet controller pushes the live '
                             'set via POST /kv/peers instead)')
    parser.add_argument('--kv-spill-bytes', type=int, default=0,
                        metavar='B',
                        help='tiered prefix cache: spill evicted KV '
                             'pages (payload + scales + chain key) '
                             'into a host-RAM LRU of at most B bytes '
                             'instead of dropping them; a later '
                             'chain-key hit restores the exact bytes '
                             '(bit-identical to fresh compute). 0 = '
                             'off. Needs --continuous-batching')
    parser.add_argument('--kv-cold-dir', default=None, metavar='DIR',
                        help='cold tier behind --kv-spill-bytes: '
                             'pages LRU-evicted from host RAM land '
                             'in DIR (local path or gs:// prefix) '
                             'and survive process restarts — meant '
                             'for giant shared system prompts')
    parser.add_argument('--drain-grace', type=float, default=630.0,
                        help='SIGTERM drain: seconds to wait for '
                             'in-flight requests before exiting. The '
                             'default exceeds the request-timeout '
                             'default so a worst-case generation still '
                             'completes; requests outliving the grace '
                             'window are dropped at exit')
    parser.add_argument('--request-timeout', type=float, default=600.0,
                        help='per-request deadline ceiling, seconds: '
                             'requests carrying a smaller `timeout` '
                             'body field use that, anything else (and '
                             'anything larger) is clamped here. '
                             'Expired requests are reaped mid-decode '
                             'and answered 504')
    parser.add_argument('--stream-final', default='rows',
                        choices=('rows', 'lengths'),
                        help='what a token stream\'s terminal event '
                             'carries: `rows` = {"done": true, '
                             '"tokens": [full rows]} (prompt and '
                             'generated, as the non-streaming '
                             'endpoint returns); `lengths` = {"done": '
                             'true, "lengths": [n, ...]} — for long '
                             'prompts, whose rows make one SSE line '
                             'of tens of KB that a line-buffered '
                             'client refuses (asyncio: 64 KiB)')
    parser.add_argument('--max-queue-requests', type=int, default=0,
                        metavar='N',
                        help='admission control: shed (429 + '
                             'Retry-After) once N requests are '
                             'waiting for a decode slot. 0 = '
                             'unbounded (the pre-hardening behavior)')
    parser.add_argument('--max-queue-tokens', type=int, default=0,
                        metavar='T',
                        help='admission control: shed once the queued '
                             'prompts hold T tokens (a token-aware '
                             'bound sheds one 4k-prompt instead of '
                             'forty short ones). 0 = unbounded')
    parser.add_argument('--fault-plan', default=None, metavar='JSON',
                        help='chaos testing: a fault plan (inline '
                             'JSON or a path to a JSON file) arming '
                             'the skypilot_tpu.robustness.faults '
                             'injection points in this process; see '
                             'docs/guides.md "Serving robustness". '
                             'Equivalent to the STPU_FAULT_PLAN env '
                             'var. Never set this in production')
    parser.add_argument('--trace-sample', type=float, default=0.0,
                        metavar='P',
                        help='distributed request tracing: sample '
                             'this fraction of requests (0..1) into '
                             'Chrome-trace spans, served at GET '
                             '/debug/trace/<id> and merged across '
                             'processes by `stpu trace`. Requests '
                             'arriving with an x-skypilot-trace '
                             'header are always traced (the caller '
                             'already paid the sampling decision). '
                             '0 = off (zero overhead)')
    parser.add_argument('--trace-seed', type=int, default=None,
                        help='seed the trace sampler: the sampled '
                             'set and its ids become reproducible')
    parser.add_argument('--slo', default=None, metavar='SPEC',
                        help='declarative serving SLOs, e.g. '
                             '"p99_ttft_ms=500,p99_itl_ms=100,'
                             'error_rate=0.01,shed_rate=0.05": '
                             '/stats grows an `slo` section with '
                             'multi-window burn rates and the '
                             'skypilot_serving_slo_* gauges go live '
                             '(docs/guides.md "Tracing & SLOs")')
    parser.add_argument('--cpu', action='store_true',
                        help='pin the CPU backend (smoke/dev runs)')
    args = parser.parse_args()
    if args.slo:
        # Fail fast at startup, not at first scrape.
        from skypilot_tpu.observability import slo as slo_lib
        try:
            slo_lib.parse_slo(args.slo)
        except ValueError as e:
            parser.error(str(e))
    if args.decode_chunk > 1 and not args.continuous_batching:
        parser.error('--decode-chunk is a continuous-engine knob; '
                     'add --continuous-batching (the one-shot engine '
                     'would silently ignore it)')
    if args.kv_dtype == 'int8' and not args.continuous_batching:
        parser.error('--kv-dtype int8 requires --continuous-batching '
                     '(the one-shot engine decodes through the dense '
                     'per-slot cache, which has no scale storage)')
    if (args.kv_spill_bytes or args.kv_cold_dir) and \
            not args.continuous_batching:
        parser.error('--kv-spill-bytes/--kv-cold-dir require '
                     '--continuous-batching (the spill tier stores '
                     'evicted prefix-cache pages of the paged slot '
                     'engine)')
    if args.role == 'prefill' and not args.continuous_batching:
        parser.error('--role prefill requires --continuous-batching '
                     '(the handoff exports KV page chains from the '
                     'slot engine\'s prefix cache)')
    if args.stages > 1:
        if not args.continuous_batching:
            parser.error('--stages requires --continuous-batching '
                         '(pipeline serving runs the paged slot '
                         'engine; the one-shot path has no microbatch '
                         'stream to fill the stage bubble)')
        if args.weight_dtype == 'int8':
            parser.error('--stages does not compose with '
                         '--weight-dtype int8 (the quantized wrapper '
                         'has no per-stage split; use int8 KV pages '
                         'via --kv-dtype int8 instead)')
        if args.decode_chunk > 1:
            parser.error('--stages does not compose with '
                         '--decode-chunk > 1 (the in-flight group '
                         'ring feeds one token per slot per round)')
        if args.num_slots % args.stages != 0:
            parser.error(f'--num-slots {args.num_slots} must divide '
                         f'evenly into --stages {args.stages} slot '
                         f'groups (the decode ring assigns '
                         f'num_slots/stages slots per group)')

    if args.fault_plan:
        from skypilot_tpu.robustness import faults
        faults.install_plan(args.fault_plan)
        print(f'serve_lm: FAULT PLAN ARMED '
              f'({sorted(faults.stats())}) — chaos mode', flush=True)

    from skypilot_tpu.inference.http_server import serve
    from skypilot_tpu.inference.runtime import build_runtime
    serve(build_runtime(args), args.port,
          drain_grace=args.drain_grace, zone=args.zone)


if __name__ == '__main__':
    main()
