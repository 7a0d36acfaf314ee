"""AST invariant lint: CLAUDE.md contracts compiled into machine-checked
rules.

Each rule encodes one convention-only invariant that has already bitten
(or nearly bitten) a past round — the `rpc/services.py` ad-hoc retry
loop, the donation-set pin, the failpoint/trace decision-boundary
discipline, the no-int64-in-kernels rule, and the lock/condition
factory seam the runtime detector (lockgraph.py) depends on. The FLOW-sensitive
contracts (copy-before-mutate through aliases/containers, the
tracked-encoder dirty feed, barrier-before-drain ordering) live in
analysis/dataflow.py on a real CFG; `all_rules()` is the combined set
and the default for every driver entry point.

Suppression is per-line and per-rule:

    time.sleep(0.1)   # lint: allow(ad-hoc-sleep) not a retry loop: ...

A pragma on the flagged line or the line directly above it silences
exactly the named rule(s); `# lint: allow(rule-a, rule-b)` names several.
Every allow is expected to carry a justification in the same comment —
the pragma names WHAT is silenced, the prose says WHY it is safe.

Run as a tier-1 test (tests/test_lint_clean.py: the tree must be clean
modulo pragmas) and standalone:

    python -m swarmkit_tpu.analysis          # lint + mirror drift check

Adding a rule: subclass Rule, set `name` / `invariant` / `applies()`,
yield Findings from `check()`, append to RULES, add a must-fire and a
must-not-fire fixture to tests/test_analysis.py, and document it in
docs/static_analysis.md.
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, Iterator

PRAGMA_RE = re.compile(r"#\s*lint:\s*allow\(([a-z0-9_\-,\s]+)\)")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative posix path
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Module:
    """One parsed source file + its pragma map."""

    def __init__(self, path: str, source: str):
        self.path = path                      # relative posix
        self.source = source
        self.tree = ast.parse(source, filename=path)
        # line -> frozenset(rule names allowed on that line)
        self.allows: dict[int, frozenset[str]] = {}
        # lines that are comment-only: the ONLY form whose pragma also
        # covers the following line — a trailing pragma on a CODE line
        # must not spill onto its neighbor
        self._comment_only: set[int] = set()
        for i, text in enumerate(source.splitlines(), start=1):
            m = PRAGMA_RE.search(text)
            if m:
                rules = frozenset(
                    r.strip() for r in m.group(1).split(",") if r.strip())
                self.allows[i] = rules
                if text.lstrip().startswith("#"):
                    self._comment_only.add(i)

    def allowed(self, rule: str, line: int) -> bool:
        """Pragma on the flagged line, or on a comment-only line
        directly above it."""
        if rule in self.allows.get(line, ()):
            return True
        return (line - 1 in self._comment_only
                and rule in self.allows.get(line - 1, ()))


class Rule:
    name: str = ""
    invariant: str = ""      # the CLAUDE.md contract this rule enforces

    def applies(self, path: str) -> bool:
        return True

    def check(self, mod: Module) -> Iterator[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, mod: Module, node: ast.AST, msg: str) -> Finding:
        return Finding(self.name, mod.path, getattr(node, "lineno", 0), msg)


def _attr_chain(node: ast.AST) -> str:
    """Dotted name for Name/Attribute chains ('jax.sharding.set_mesh');
    '' for anything dynamic."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _walk_with_parents(tree: ast.AST) -> Iterator[tuple[ast.AST, list]]:
    """Yield (node, ancestor_stack) — ancestors outermost-first."""
    stack: list[ast.AST] = []

    def rec(node):
        yield node, stack
        stack.append(node)
        for child in ast.iter_child_nodes(node):
            yield from rec(child)
        stack.pop()

    yield from rec(tree)


# --------------------------------------------------------------------- rules
class AdHocSleepRule(Rule):
    """Caller-side waits are explicit Backoff policies / Clock timers —
    no new ad-hoc sleep loops (PR 3 contract)."""

    name = "ad-hoc-sleep"
    invariant = ("retries/waits go through utils/backoff.py Backoff or "
                 "utils/clock.py Clock (clock-injectable, test-"
                 "deterministic) — never bare time.sleep")

    ALLOWED = (
        "swarmkit_tpu/utils/backoff.py",     # the policy seam itself
        "swarmkit_tpu/utils/clock.py",       # the Clock seam
        "swarmkit_tpu/utils/failpoints.py",  # armed-only injected latency
        "swarmkit_tpu/cmd/",                 # CLI entrypoints (human pacing)
    )

    def applies(self, path: str) -> bool:
        return (path.startswith("swarmkit_tpu/")
                and not path.startswith(self.ALLOWED))

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain.split(".")[0] in ("backoff", "_backoff"):
                continue      # the clock-driven seam itself
            if chain.endswith(".sleep") or chain == "sleep":
                yield self.finding(
                    mod, node,
                    f"bare {chain or 'sleep'}() — use a utils/backoff.py "
                    "Backoff policy or a utils/clock.py timer")


class DonatePinnedRule(Rule):
    """Donation sets in kernel jits are pinned to the 8 STATE arrays."""

    name = "donate-pinned"
    invariant = ("every donate_argnums in ops/ must be the "
                 "DONATE_STATE_ARGNUMS constant — donating a group-table "
                 "position would hand the kernel invalidated buffers on "
                 "a _gcache hit")

    def applies(self, path: str) -> bool:
        return path.startswith(("swarmkit_tpu/ops/", "swarmkit_tpu/models/",
                                "swarmkit_tpu/parallel/"))

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg != "donate_argnums":
                    continue
                if not (isinstance(kw.value, ast.Name)
                        and kw.value.id == "DONATE_STATE_ARGNUMS"):
                    yield self.finding(
                        mod, kw.value,
                        "donate_argnums must be DONATE_STATE_ARGNUMS "
                        "(the 8 STATE arrays; group tables are cached "
                        "and must never be donated)")


class SpanInLoopRule(Rule):
    """Trace/failpoint/lifecycle sites live at decision boundaries,
    never inside per-entry hot loops; per-entry emission must be guarded
    by the `.enabled()` pattern so the disarmed cost stays one
    truthiness test (CLAUDE.md trace-plane + lifecycle-plane
    contracts — the scheduler records ONE batch per wave, never per
    placed task in the walk)."""

    name = "span-in-loop"
    invariant = ("no trace.span/start/rec/annotate, failpoints.fp*, "
                 "lifecycle.record*, or telemetry snapshot-assembly "
                 "call inside a for/while body in the audited hot "
                 "modules unless under an `if trace.enabled()` / "
                 "`if lifecycle.enabled()` / `if telemetry.enabled()` "
                 "/ `if traced:` guard")

    AUDITED = (
        "swarmkit_tpu/ops/pipeline.py",
        "swarmkit_tpu/ops/commit.py",
        "swarmkit_tpu/ops/resident.py",
        "swarmkit_tpu/scheduler/scheduler.py",
        "swarmkit_tpu/scheduler/batch.py",
        "swarmkit_tpu/scheduler/encode.py",
        "swarmkit_tpu/raft/node.py",
        "swarmkit_tpu/raft/storage.py",
        "swarmkit_tpu/dispatcher/dispatcher.py",
        "swarmkit_tpu/dispatcher/heartbeat.py",
        "swarmkit_tpu/dispatcher/follower.py",
        "swarmkit_tpu/dispatcher/columnar_diff.py",
        "swarmkit_tpu/rpc/wire.py",
        "swarmkit_tpu/rpc/server.py",
        "swarmkit_tpu/rpc/client.py",
        "swarmkit_tpu/agent/agent.py",
        "swarmkit_tpu/logbroker/broker.py",
        "swarmkit_tpu/logbroker/sharded.py",
        "swarmkit_tpu/watchapi/watch.py",
    )
    TRACE_CALLS = frozenset({"span", "start", "rec", "annotate", "wrap"})
    FP_CALLS = frozenset({"fp", "fp_value", "fp_transform"})
    LIFECYCLE_CALLS = frozenset({"record", "record_batch", "record_pairs"})
    # telemetry snapshot assembly (ISSUE 15): the heartbeat loop builds
    # a snapshot every Kth beat — the build must sit under the
    # `if telemetry.enabled():` guard so a disarmed beat allocates
    # nothing
    TELEMETRY_CALLS = frozenset({"node_snapshot", "registry_snapshot"})

    def applies(self, path: str) -> bool:
        return path in self.AUDITED

    @staticmethod
    def _guarded(ancestors: list, loop_idx: int) -> bool:
        """True when an If between the innermost loop and the call tests
        the armed state (`if traced:` / `if trace.enabled():`)."""
        for anc in ancestors[loop_idx + 1:]:
            if not isinstance(anc, ast.If):
                continue
            for n in ast.walk(anc.test):
                if isinstance(n, ast.Name) and n.id == "traced":
                    return True
                if isinstance(n, ast.Attribute) and n.attr == "enabled":
                    return True
        return False

    def check(self, mod: Module) -> Iterator[Finding]:
        for node, ancestors in _walk_with_parents(mod.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            base = node.func.value
            base_name = base.id if isinstance(base, ast.Name) else ""
            is_site = (
                (base_name == "trace"
                 and node.func.attr in self.TRACE_CALLS)
                or (base_name == "failpoints"
                    and node.func.attr in self.FP_CALLS)
                or (base_name == "lifecycle"
                    and node.func.attr in self.LIFECYCLE_CALLS)
                or (base_name == "telemetry"
                    and node.func.attr in self.TELEMETRY_CALLS))
            if not is_site:
                continue
            # innermost enclosing loop that is inside the same function
            # as the call (a call in a nested def is NOT "in" an outer
            # function's loop — the def body runs elsewhere)
            loop_idx = None
            for i in range(len(ancestors) - 1, -1, -1):
                anc = ancestors[i]
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)):
                    break
                if isinstance(anc, (ast.For, ast.While)):
                    loop_idx = i
                    break
            if loop_idx is None:
                continue
            if self._guarded(ancestors, loop_idx):
                continue
            yield self.finding(
                mod, node,
                f"{base_name}.{node.func.attr} inside a loop body — "
                "hot-path sites live at decision boundaries; per-entry "
                f"emission needs the `if {base_name}.enabled():` guard")


class RawConditionRule(Rule):
    """The lockgraph detector's documented Condition blind spot
    (ISSUE 12): a bare `threading.Condition()` allocates an internal
    RLock the armed detector can never see, so an inversion involving
    only that lock produces no edges. Every Condition must be
    constructed over a lockgraph factory primitive."""

    name = "raw-condition"
    invariant = ("threading.Condition() must wrap a "
                 "lockgraph.make_lock/make_rlock primitive "
                 "(threading.Condition(make_rlock(name))) so the armed "
                 "lock-order detector sees its acquisitions; disarmed "
                 "the factory hands back the plain primitive — one "
                 "truthiness test, zero tracker allocations")

    FACTORIES = frozenset({"make_lock", "make_rlock"})

    def applies(self, path: str) -> bool:
        return (path.startswith("swarmkit_tpu/")
                and not path.startswith("swarmkit_tpu/analysis/"))

    def _lock_arg_ok(self, node: ast.Call) -> bool:
        """The lock argument (positional 0 or lock=) must be a direct
        factory call or a name/attribute (assumed factory-made — the
        raw-lock rule polices how names get bound)."""
        arg = None
        if node.args:
            arg = node.args[0]
        for kw in node.keywords:
            if kw.arg == "lock":
                arg = kw.value
        if arg is None:
            return False
        if isinstance(arg, ast.Call):
            fn = arg.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else "")
            return name in self.FACTORIES
        # a pre-built lock passed by name: raw-lock already guarantees
        # every lock binding routes through the factory
        return isinstance(arg, (ast.Name, ast.Attribute))

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain == "threading.Condition" \
                    and not self._lock_arg_ok(node):
                yield self.finding(
                    mod, node,
                    "bare threading.Condition() — its internal RLock "
                    "is invisible to the lock-order detector; use "
                    "threading.Condition(lockgraph.make_rlock(name))")
            if isinstance(node.func, ast.Name) \
                    and node.func.id == "Condition" \
                    and not self._lock_arg_ok(node):
                yield self.finding(
                    mod, node,
                    "bare Condition() — wrap a lockgraph factory "
                    "primitive: threading.Condition(make_rlock(name))")


class Int64InKernelRule(Rule):
    """int64 is unavailable in kernels (no x64 on the TPU backend)."""

    name = "int64-in-kernel"
    invariant = ("kernel modules never touch int64 — jnp has no x64 "
                 "here; host-side staging arrays live outside these "
                 "modules")

    KERNEL_MODULES = (
        "swarmkit_tpu/ops/placement.py",
        "swarmkit_tpu/ops/reconcile.py",
        "swarmkit_tpu/ops/bitpack.py",
        "swarmkit_tpu/ops/raft_replay.py",
        "swarmkit_tpu/ops/alloc.py",
        "swarmkit_tpu/models/cluster_step.py",
    )

    def applies(self, path: str) -> bool:
        return path in self.KERNEL_MODULES

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Attribute) and node.attr == "int64":
                yield self.finding(
                    mod, node,
                    "int64 in a kernel module — kernels run without x64; "
                    "use int32 (jnp.argsort is stable for tie-breaks)")


class RawLockRule(Rule):
    """Lock creation routes through the lockgraph factory seam so the
    armed lock-order detector sees every acquisition."""

    name = "raw-lock"
    invariant = ("threading.Lock()/RLock() sites go through "
                 "analysis.lockgraph.make_lock/make_rlock — the factory "
                 "is what lets the armed detector shim acquisition "
                 "order; disarmed it returns the plain primitive")

    def applies(self, path: str) -> bool:
        return (path.startswith("swarmkit_tpu/")
                and not path.startswith("swarmkit_tpu/analysis/"))

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            # `from threading import Lock` would let a bare Lock() call
            # bypass the dotted-form check below — flag the import, the
            # only gateway to that spelling
            if isinstance(node, ast.ImportFrom) \
                    and node.module == "threading":
                for alias in node.names:
                    if alias.name in ("Lock", "RLock"):
                        yield self.finding(
                            mod, node,
                            f"`from threading import {alias.name}` — a "
                            "bare call would bypass the lockgraph "
                            "factory seam; import threading and route "
                            "through analysis.lockgraph")
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if chain in ("threading.Lock", "threading.RLock"):
                kind = chain.rsplit(".", 1)[1]
                factory = "make_lock" if kind == "Lock" else "make_rlock"
                yield self.finding(
                    mod, node,
                    f"bare threading.{kind}() — route through "
                    f"analysis.lockgraph.{factory}(name) so the armed "
                    "lock-order detector can track it")


class ColumnarMutateRule(Rule):
    """The columnar task mirror is derived truth kept in lockstep by the
    commit path (docs/store.md): a direct array write anywhere else
    silently diverges the columns from the object table."""

    name = "columnar-mutate"
    invariant = ("columnar arrays (store.columnar.*) are written ONLY by "
                 "the columnar plane itself — store/columnar.py, the "
                 "store commit/wave path in store/memory.py, and the "
                 "batched allocator (allocator/batched.py, ops/alloc.py); "
                 "everyone else goes through assign_wave / the commit "
                 "lockstep or reads")

    ALLOWED = (
        "swarmkit_tpu/store/columnar.py",
        "swarmkit_tpu/store/memory.py",
        "swarmkit_tpu/allocator/batched.py",
        "swarmkit_tpu/ops/alloc.py",
        # ISSUE 16: the diff gate owns per-shard plan columns (reads of
        # store.columnar plus its own arrays; never writes the mirror)
        "swarmkit_tpu/dispatcher/columnar_diff.py",
    )

    def applies(self, path: str) -> bool:
        return (path.startswith("swarmkit_tpu/")
                and path not in self.ALLOWED)

    @staticmethod
    def _chain_of_target(node: ast.AST) -> str:
        """Dotted chain of an assignment target, unwrapping subscripts
        (`store.columnar.state[rows]` -> 'store.columnar.state')."""
        while isinstance(node, ast.Subscript):
            node = node.value
        return _attr_chain(node)

    def check(self, mod: Module) -> Iterator[Finding]:
        tainted: set[str] = set()
        # SOURCE order, not ast.walk's breadth-first order: an alias
        # bound inside a nested block (if/with/try) would otherwise be
        # visited AFTER a shallower write through it and the write
        # would escape the taint
        stmts = sorted(
            (n for n in ast.walk(mod.tree)
             if isinstance(n, (ast.Assign, ast.AugAssign))),
            key=lambda n: (n.lineno, n.col_offset))
        for node in stmts:
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
                # taint names bound to a .columnar read so writes
                # through the alias are caught too
                value_chain = _attr_chain(node.value)
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        if value_chain.split(".")[-1:] == ["columnar"]:
                            tainted.add(tgt.id)
                        else:
                            tainted.discard(tgt.id)
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for tgt in targets:
                if isinstance(tgt, ast.Name):
                    continue
                chain = self._chain_of_target(tgt)
                parts = chain.split(".") if chain else []
                hit = "columnar" in parts \
                    or (parts and parts[0] in tainted and len(parts) > 1)
                if hit:
                    yield self.finding(
                        mod, tgt,
                        f"direct write through {chain!r} — columnar "
                        "arrays are commit-path-owned derived truth; "
                        "use store.assign_wave / the commit lockstep "
                        "(docs/store.md)")


class RawMetricRule(Rule):
    """Metric families are constructed ONLY through the utils/metrics
    module factories (ISSUE 15): a directly-constructed
    Histogram/Counter/CounterFamily/HistogramFamily never enters the
    process registry, so the per-node /metrics exposition AND the
    cluster telemetry rollup silently miss it."""

    name = "raw-metric"
    invariant = ("Histogram/Counter/CounterFamily/HistogramFamily are "
                 "instantiated only inside utils/metrics.py — every "
                 "other module uses the factories (histogram(), "
                 "counter(), counter_family(), histogram_family()) so "
                 "the family is registry-visible to the exposition and "
                 "the telemetry rollup")

    CLASSES = frozenset({"Histogram", "Counter", "CounterFamily",
                         "HistogramFamily"})

    def applies(self, path: str) -> bool:
        # tests may build standalone families (codec fixtures, per-node
        # parity registries); product code may not
        return (path.startswith("swarmkit_tpu/")
                and path != "swarmkit_tpu/utils/metrics.py")

    def check(self, mod: Module) -> Iterator[Finding]:
        # names imported FROM a metrics module: `from ..utils.metrics
        # import Histogram` (collections.Counter and friends stay
        # invisible — only the metrics module's classes are policed)
        imported: dict[str, str] = {}   # bound name -> class name
        # names the metrics MODULE itself is bound to: `from ..utils
        # import metrics [as m]`, `import swarmkit_tpu.utils.metrics
        # as m` — an aliased module must not smuggle m.Histogram(...)
        mod_aliases: set[str] = {"metrics"}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.split(".")[-1] == "metrics":
                for alias in node.names:
                    if alias.name in self.CLASSES:
                        imported[alias.asname or alias.name] = alias.name
            elif isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if alias.name == "metrics":
                        mod_aliases.add(alias.asname or alias.name)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[-1] == "metrics" \
                            and alias.asname:
                        mod_aliases.add(alias.asname)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            name = None
            if isinstance(fn, ast.Name) and fn.id in imported:
                name = imported[fn.id]
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr in self.CLASSES:
                parts = _attr_chain(fn).split(".")
                if len(parts) >= 2 and parts[-2] in mod_aliases:
                    name = fn.attr
            if name is not None:
                factory = {
                    "Histogram": "histogram",
                    "Counter": "counter",
                    "CounterFamily": "counter_family",
                    "HistogramFamily": "histogram_family",
                }[name]
                yield self.finding(
                    mod, node,
                    f"direct {name}(...) construction — route through "
                    f"utils.metrics.{factory}(name) so the family is "
                    "registry-visible (exposition + telemetry rollup)")


RULES: tuple[Rule, ...] = (
    AdHocSleepRule(),
    DonatePinnedRule(),
    SpanInLoopRule(),
    Int64InKernelRule(),
    RawLockRule(),
    RawConditionRule(),
    ColumnarMutateRule(),
    RawMetricRule(),
)


def all_rules() -> tuple[Rule, ...]:
    """The full rule set: the syntactic rules above plus the dataflow
    contract rules (analysis/dataflow.py). Lazy import — dataflow
    builds on this module, so a top-level import would be circular."""
    from . import dataflow

    return RULES + dataflow.RULES


# -------------------------------------------------------------------- driver
def lint_source(source: str, path: str,
                rules: Iterable[Rule] | None = None) -> list[Finding]:
    """Lint one in-memory source blob (the fixture-test entrypoint).
    Default rule set is `all_rules()` (syntactic + dataflow)."""
    if rules is None:
        rules = all_rules()
    mod = Module(path, source)
    out: list[Finding] = []
    for rule in rules:
        if not rule.applies(path):
            continue
        for f in rule.check(mod):
            if not mod.allowed(rule.name, f.line):
                out.append(f)
    # dedupe identical findings (a statement can own several CFG nodes
    # — e.g. a finally body cloned onto an abrupt-exit path)
    out = list(dict.fromkeys(out))
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    return out


def iter_py_files(root: Path, subdirs: Iterable[str]) -> Iterator[Path]:
    for sub in subdirs:
        base = root / sub
        if not base.exists():
            continue
        for p in sorted(base.rglob("*.py")):
            if "__pycache__" in p.parts:
                continue
            yield p


def lint_tree(root: Path, subdirs=("swarmkit_tpu", "tests"),
              rules: Iterable[Rule] | None = None) -> list[Finding]:
    """Lint the repo tree. `root` is the repo root; paths in findings
    are repo-relative posix (what `applies()` matches on)."""
    if rules is None:
        rules = all_rules()
    findings: list[Finding] = []
    for p in iter_py_files(root, subdirs):
        rel = p.relative_to(root).as_posix()
        findings.extend(_lint_path(root, rel, rules))
    return findings


def _lint_path(root: Path, rel: str, rules: Iterable[Rule],
               ) -> list[Finding]:
    try:
        source = (root / rel).read_text()
    except (OSError, UnicodeDecodeError):       # unreadable: not lintable
        return []
    try:
        return lint_source(source, rel, rules)
    except SyntaxError:
        return [Finding("parse-error", rel, 0, "file does not parse")]


def lint_files(root: Path, rel_paths: Iterable[str],
               rules: Iterable[Rule] | None = None) -> list[Finding]:
    """Lint an explicit repo-relative file list (the `--changed-only`
    scope). Every rule is per-file, so findings for a file here are
    IDENTICAL to that file's slice of the full `lint_tree` pass — the
    scope-soundness guard in tests/test_lint_clean.py pins it."""
    if rules is None:
        rules = all_rules()
    findings: list[Finding] = []
    for rel in sorted(set(rel_paths)):
        if not rel.endswith(".py"):
            continue
        findings.extend(_lint_path(root, rel, rules))
    return findings
