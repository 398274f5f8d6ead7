"""Rule configuration: which modules are hot paths, which scopes are
host-sync-free, what counts as a param-valued name.

Kept in one place (not scattered through the rules) so the registered
invariants read as a contract: adding a module to HOT_JIT_MODULES or a
function to HOT_SYNC_SCOPES *is* the act of putting it under the
discipline — see docs/design/static_analysis.md for the policy.
"""

import re

# -- D9D001: bare jax.jit must be tracked_jit here ----------------------
# The hot-path surface: the serving/training loop layers, the PP
# runtime, and the ops wrappers. Everything the recompile guard and the
# per-executable HBM inventory are supposed to see (tracked_jit,
# telemetry/introspect.py). Cold init/export sites inside these modules
# carry reasoned inline suppressions instead of exemptions.
HOT_JIT_MODULES: tuple[str, ...] = (
    "d9d_tpu/loop/",
    "d9d_tpu/pipelining/",
    "d9d_tpu/ops/",
)

# -- D9D003: registered hot scopes (one-dispatch-one-readback loops) ----
# (path prefix, qualname regex). A scope registered here promises the
# host does no synchronous device work beyond its accounted readbacks;
# each accounted readback carries an inline suppression naming itself.
HOT_SYNC_SCOPES: tuple[tuple[str, str], ...] = (
    # serve chunk loop: dispatch + harvest, and what they call every
    # chunk in the cache manager and the accounting
    ("d9d_tpu/loop/serve.py", r"ContinuousBatcher\._dispatch_chunk"),
    ("d9d_tpu/loop/serve.py", r"ContinuousBatcher\._harvest_one"),
    ("d9d_tpu/loop/serve.py", r"ContinuousBatcher\.step_chunk"),
    ("d9d_tpu/loop/serve.py", r"ContinuousBatcher\._drain_impl"),
    ("d9d_tpu/loop/serve_cache.py",
     r"CacheManager\.(admit|mark_filled|release_row|flush_deferred)"),
    ("d9d_tpu/loop/serve_accounting.py", r"ServeAccounting\.note_.*"),
    # speculative decode round (one dispatch/readback per round)
    ("d9d_tpu/loop/speculative.py", r".*"),
    # train step builders: everything in the module is traced or
    # dispatch-adjacent
    ("d9d_tpu/loop/train_step.py", r".*"),
    # PP per-microbatch executor: the single-controller dispatch loop
    ("d9d_tpu/pipelining/runtime/executor.py",
     r"PipelineScheduleExecutor\.(step|_act_.*|_put|_stage_kwargs)"),
    # fused MPMD runtime: the whole dispatch loop is a handful of
    # compiled runs — any host sync between them stalls every rank
    ("d9d_tpu/pipelining/runtime/fused.py",
     r"FusedPipelineExecutor\.(step|_stage_ext|_mesh_scope)"),
    # PP stage runtime: per-action jit surfaces
    ("d9d_tpu/pipelining/runtime/stage.py", r"PipelineStageRuntime\..*"),
    # PP optimizer step path (scalar hops must stay in XLA's stream)
    ("d9d_tpu/pipelining/training.py",
     r"PipelinedOptimizer\.(step|step_guarded)"),
)

# host-sync call surfaces (canonical names / .attr tails)
SYNC_CALLS: tuple[str, ...] = (
    "jax.device_get",
    "jax.block_until_ready",
    ".block_until_ready",
    ".item",
)
# numpy materializers: a sync only when fed a device value — the rule
# flags them when the argument came out of a Call (dataflow), so
# np.asarray([host, list]) marshalling stays clean
NUMPY_MATERIALIZERS: tuple[str, ...] = (
    "numpy.asarray",
    "numpy.array",
)
# float()/int()/bool() casts: flagged only on values the lightweight
# dataflow tagged device-valued (assigned from a jax.* call)
CAST_NAMES: tuple[str, ...] = ("float", "int", "bool")
DEVICE_PRODUCER_PREFIXES: tuple[str, ...] = ("jax.",)

# -- D9D002: param-valued names ------------------------------------------
# A closure-captured free variable matching this (or assigned from an
# attribute matching it) is treated as param/array-valued: baked into
# the jitted program as a constant, it silently pins the weights the
# executable uses — the PR 8 install_weights class.
PARAM_NAME_RE = re.compile(
    r"(?:^|_)(?:params?|weights|opt_state|masters?|adapters?|"
    r"param_tree|state_tree|kv_cache)(?:$|_)"
)
# free names assigned from calls with these canonical prefixes are
# array-valued even when their name says nothing
ARRAY_PRODUCER_PREFIXES: tuple[str, ...] = (
    "jax.numpy.",
    "jax.random.",
    "jax.device_put",
)

# -- D9D008: per-action stage dispatch in the pipeline runtime ----------
# Path prefixes under the fused-runtime dispatch discipline: host code
# here must not call the PipelineStageRuntime per-action jit wrappers
# (one TrackedJit dispatch per schedule action — the single-controller
# tax runtime/fused.py removed); fused runs trace the raw ``_*_impl``
# bodies under one jit instead. The legacy interpreter's call sites
# carry inline suppressions naming the parity-oracle debt.
PER_ACTION_DISPATCH_PATHS: tuple[str, ...] = (
    "d9d_tpu/pipelining/runtime/",
)
# the per-action jit surfaces of PipelineStageRuntime (stage.py)
PER_ACTION_DISPATCH_ATTRS: tuple[str, ...] = (
    "forward",
    "forward_loss",
    "forward_out",
    "backward_full",
    "backward_input",
    "backward_weight",
    "backward_input_acts",
    "backward_weight_acts",
    "accumulate",
    "cast_grads",
)

# -- D9D004: state init under jit ---------------------------------------
PLACEMENT_NORMALIZERS: tuple[str, ...] = (
    ".replicate_uncommitted",
    "replicate_uncommitted",
)

# -- D9D005: nondeterminism inside traced functions ---------------------
NONDETERMINISM_CALLS: tuple[str, ...] = (
    "time.time",
    "time.time_ns",
    "time.perf_counter",
    "time.perf_counter_ns",
    "time.monotonic",
    "time.monotonic_ns",
    "datetime.datetime.now",
    "datetime.datetime.utcnow",
    "random.",      # stdlib random module, any function
    "numpy.random.",
    "os.urandom",
    "uuid.uuid4",
    "secrets.",
)

# -- D9D006: telemetry namespace discipline -----------------------------
# attribute names whose first argument is a metric/span name literal;
# includes ServeAccounting's replica-label-aware wrappers
INSTRUMENT_CALL_ATTRS: tuple[str, ...] = (
    "counter",
    "gauge",
    "gauge_fn",
    "histogram",
    "observe",
    "record_value",
    "span",
    "record_span",
    "counter_add",
    "gauge_set",
)
# receivers that are NOT the telemetry hub despite sharing attr names
INSTRUMENT_RECEIVER_DENYLIST: tuple[str, ...] = (
    "argparse",
    "parser",
)
OBSERVABILITY_DOC = "docs/design/observability.md"
# names legitimate outside the doc's tables (engine-internal seams)
EXTRA_ALLOWED_METRIC_NAMES: tuple[str, ...] = ()
# the path-free-label rule (PR 9): replica labels become one path
# segment of serve/{label}/..., so they must not contain '/'
LABEL_CALL_NAMES: tuple[str, ...] = ("set_replica_label",)
LABEL_KWARGS: tuple[str, ...] = ("replica_label",)
