#!/bin/bash
# The canonical tier-1 gate: runs the EXACT "Tier-1 verify" line from
# ROADMAP.md, so builders, CI, and the driver all invoke one entry point
# instead of each retyping (and drifting from) the command.  Keep this in
# lockstep with ROADMAP.md.
#
# Output contract: the test log tees to /tmp/_t1.log and the final line
# prints DOTS_PASSED=<n> (count of passing tests); the exit code is
# pytest's.
cd "$(dirname "$0")/.." || exit 1

# --- fsck smoke (integrity layer, ISSUE 2) -------------------------------
# Build a tiny artifact set, assert `sheep fsck` passes it clean, corrupt
# one artifact, assert fsck exits nonzero.  Seconds of work; a regression
# in the end-to-end integrity path fails the gate before pytest even runs.
FSCK_DIR=$(mktemp -d)
if env JAX_PLATFORMS=cpu python - "$FSCK_DIR" <<'EOF'
import sys, numpy as np
from sheep_tpu.io import write_edges, write_sequence, write_tree
from sheep_tpu.core import build_forest, degree_sequence
d = sys.argv[1]
tail = np.array([0, 1, 2, 3, 0], np.uint32)
head = np.array([1, 2, 3, 0, 2], np.uint32)
write_edges(d + "/g.dat", tail, head)
seq = degree_sequence(tail, head)
write_sequence(seq, d + "/g.seq")
f = build_forest(tail, head, seq)
write_tree(d + "/g.tre", f.parent, f.pst_weight)
EOF
then
  if ! env JAX_PLATFORMS=cpu bin/fsck -q "$FSCK_DIR" > /dev/null; then
    echo "FSCK SMOKE FAILED: clean artifacts did not pass fsck" >&2
    rm -rf "$FSCK_DIR"; exit 1
  fi
  # flip one record byte in the tree; fsck must now exit nonzero
  python -c "
import sys
p = sys.argv[1] + '/g.tre'
b = bytearray(open(p, 'rb').read()); b[5] ^= 0xFF
open(p, 'wb').write(bytes(b))" "$FSCK_DIR"
  if env JAX_PLATFORMS=cpu bin/fsck -q "$FSCK_DIR" > /dev/null 2>&1; then
    echo "FSCK SMOKE FAILED: corrupted artifact passed fsck" >&2
    rm -rf "$FSCK_DIR"; exit 1
  fi
  rm -rf "$FSCK_DIR"
else
  echo "FSCK SMOKE FAILED: could not build the tiny artifact set" >&2
  rm -rf "$FSCK_DIR"; exit 1
fi
# -------------------------------------------------------------------------

# --- chaos smoke (tournament supervisor, ISSUE 3) ------------------------
# One kill round + one corrupt round through the supervised tournament on
# a tiny synthetic graph; the final tree must be bit-identical to the
# fault-free supervised run.  Seconds of work (in-process legs); a
# regression in the supervisor's recovery paths fails the gate before
# pytest even runs.
if ! env JAX_PLATFORMS=cpu python - <<'EOF'
import tempfile
from sheep_tpu.supervisor import (InlineRunner, SupervisorConfig,
                                  parse_fault_plan, run_supervised)
from sheep_tpu.io.edges import write_net
from sheep_tpu.utils.synth import rmat_edges

d = tempfile.mkdtemp()
tail, head = rmat_edges(6, 4 << 6, seed=5)
graph = d + "/g.net"
write_net(graph, tail, head)

def run(name, chaos=None):
    cfg = SupervisorConfig(workers=2, poll_s=0.01, backoff_base_s=0.0,
                           chaos=chaos, grammar=False)
    m = run_supervised(graph, f"{d}/{name}", cfg, runner=InlineRunner(0.05))
    with open(m.final_tree, "rb") as f:
        data = f.read()
    return data, m

base, _ = run("base")
hurt, m = run("chaos", parse_fault_plan("kill@0:0,corrupt@1:0"))
assert hurt == base, "chaos run diverged from the fault-free tree"
counts = {leg.key: leg.dispatches for leg in m.legs}
assert counts["r0.00"] == 2 and counts["r1.00"] == 2, counts
assert all(n == 1 for k, n in counts.items()
           if k not in ("r0.00", "r1.00")), counts
EOF
then
  echo "CHAOS SMOKE FAILED: supervised recovery did not reproduce the" \
       "fault-free tree" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- resource-exhaustion smoke (budgets + I/O faults, ISSUE 5) -----------
# One enospc-at-checkpoint abort + resume on the chunked build, and one
# short-write-at-publish through the supervised tournament; both must end
# bit-identical to their fault-free runs with nothing torn published.
# Seconds of work; a regression in the exhaustion/recovery paths fails
# the gate before pytest even runs.
if ! env JAX_PLATFORMS=cpu python - <<'EOF'
import os, tempfile
import numpy as np
from sheep_tpu.core.forest import build_forest
from sheep_tpu.core.sequence import degree_sequence
from sheep_tpu.io import faultfs
from sheep_tpu.io.edges import write_net
from sheep_tpu.resources import DiskExhausted
from sheep_tpu.runtime import RuntimeConfig, build_graph_resilient
from sheep_tpu.supervisor import InlineRunner, SupervisorConfig, run_supervised
from sheep_tpu.utils.synth import rmat_edges

# enospc at the second checkpoint write: typed abort, exact resume
tail, head = rmat_edges(9, 4 << 9, seed=11)
want = build_forest(tail, head, degree_sequence(tail, head))
d = tempfile.mkdtemp()
faultfs.install_plan(faultfs.parse_io_fault_plan("enospc@ckpt:1"))
try:
    build_graph_resilient(tail, head, config=RuntimeConfig(
        checkpoint_dir=d, ladder=("single", "host", "spill")))
    raise SystemExit("ENOSPC SMOKE: expected a DiskExhausted abort")
except DiskExhausted:
    pass
faultfs.clear_plan()
_, forest = build_graph_resilient(tail, head, config=RuntimeConfig(
    checkpoint_dir=d, resume=True, ladder=("single", "host", "spill")))
np.testing.assert_array_equal(forest.parent, want.parent)

# short write at a publish site of the supervised tournament: the torn
# prefix never publishes, the retried run is bit-identical
s = tempfile.mkdtemp()
t2, h2 = rmat_edges(6, 4 << 6, seed=5)
graph = s + "/g.net"
write_net(graph, t2, h2)

def run(name, plan=None):
    if plan:
        faultfs.install_plan(faultfs.parse_io_fault_plan(plan))
    cfg = SupervisorConfig(workers=2, poll_s=0.01, backoff_base_s=0.0,
                           grammar=False)
    try:
        m = run_supervised(graph, f"{s}/{name}", cfg,
                           runner=InlineRunner(0.05))
    except DiskExhausted:
        # with 2 inline workers the nth-sidecar fault index races: it
        # may land on the SUPERVISOR's own manifest write, which is a
        # typed resumable abort by the PR-5 contract — resume clean
        # (test_iofaults sweeps every site deterministically)
        faultfs.clear_plan()
        m = run_supervised(graph, f"{s}/{name}", cfg,
                           runner=InlineRunner(0.05))
    faultfs.clear_plan()
    with open(m.final_tree, "rb") as f:
        return f.read()

base = run("base")
hurt = run("hurt", plan="short@tre:0,enospc@sidecar:1")
assert hurt == base, "short-write run diverged from the fault-free tree"
EOF
then
  echo "RESOURCE SMOKE FAILED: exhaustion recovery did not reproduce the" \
       "fault-free tree" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- plateau + tail-shard smoke (reduce core, ISSUE 4) -------------------
# One forced-assist device build and one sharded-tail mesh build on a
# small R-MAT, both asserted bit-identical to the oracle.  Seconds of
# work; a regression in the round-6 reduce-core machinery fails the gate
# before pytest even runs.
if ! env JAX_PLATFORMS=cpu SHEEP_PLATEAU_FORCE=1 \
     XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'EOF'
import numpy as np
import jax.numpy as jnp
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.ops.build import prepare_links
from sheep_tpu.ops.forest import forest_fixpoint_hosted
from sheep_tpu.parallel import build_graph_chunked_distributed
from sheep_tpu.utils.synth import rmat_edges

n = 1 << 11
tail, head = rmat_edges(11, 4 * n, seed=17)
want_seq = degree_sequence(tail, head)
want = build_forest(tail, head, want_seq)
m = len(want_seq)
wantp = np.where(want.parent == 0xFFFFFFFF, n, want.parent.astype(np.int64))

# plateau scheduler (assist forced on from round one)
_, _, m_d, lo, hi, _ = prepare_links(jnp.asarray(tail, jnp.int32),
                                     jnp.asarray(head, jnp.int32), n)
parent, _ = forest_fixpoint_hosted(lo, hi, n)
np.testing.assert_array_equal(np.asarray(parent)[:m].astype(np.int64), wantp)

# sharded gather-tail over the virtual mesh
seq2, forest2 = build_graph_chunked_distributed(tail, head, num_workers=8)
np.testing.assert_array_equal(seq2, want_seq)
np.testing.assert_array_equal(forest2.parent[:m], want.parent)
EOF
then
  echo "PLATEAU/TAIL-SHARD SMOKE FAILED: round-6 reduce core diverged" \
       "from the oracle" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- threaded-native smoke (OpenMP kernels, ISSUE 14) --------------------
# Forced SHEEP_NATIVE_THREADS=4 (with the explicit oversubscription
# opt-in so the parallel code path runs even on a 1-core host, and the
# test floor so it engages at smoke size): the fused build, the
# resumable fold, and the histogram+counting-sort must be CRC-identical
# to the serial build — the deterministic per-thread partial merge.  On
# a library compiled without OpenMP the forced count resolves to 1 and
# the same assertions hold trivially (the Makefile fallback contract).
if ! env JAX_PLATFORMS=cpu SHEEP_NATIVE_THREADS=4 SHEEP_NATIVE_OVERSUB=1 \
     SHEEP_NATIVE_THREAD_FLOOR=0 python - <<'EOF'
import os
import numpy as np
from sheep_tpu import native
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.core.forest import PyLinksFold, edges_to_positions
from sheep_tpu.utils.synth import rmat_edges

n = 1 << 12
tail, head = rmat_edges(12, 6 * n, seed=41)
seq = degree_sequence(tail, head)
got = build_forest(tail, head, seq)          # forced threads (or serial)
os.environ["SHEEP_NATIVE_THREADS"] = "1"
want = build_forest(tail, head, seq)         # serial oracle arm
np.testing.assert_array_equal(got.parent, want.parent)
np.testing.assert_array_equal(got.pst_weight, want.pst_weight)

os.environ["SHEEP_NATIVE_THREADS"] = "4"
if native.available():
    m = len(seq)
    lo, hi = edges_to_positions(tail, head, seq)
    oracle = PyLinksFold(m)
    oracle.block(lo, hi)
    want_p, want_w = oracle.finish()
    linked = hi < m
    order = np.argsort(hi[linked], kind="stable")
    lo_s, hi_s = lo[linked][order], hi[linked][order]
    fold = native.LinksFold(m)
    cut = len(lo_s) // 2
    fold.block(np.concatenate([lo[~linked], lo_s[:cut]]),
               np.concatenate([hi[~linked], hi_s[:cut]]))
    fold.block(lo_s[cut:], hi_s[cut:])
    p, w = fold.finish()
    np.testing.assert_array_equal(p, want_p)
    np.testing.assert_array_equal(w, want_w)
    if native.omp_compiled():
        assert native.resolve_threads() == 4, native.resolve_threads()
print("threaded-native smoke ok (omp=%s)" % native.omp_compiled())
EOF
then
  echo "THREADED-NATIVE SMOKE FAILED: forced-thread build diverged from" \
       "the serial oracle" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- streaming-handoff smoke (hybrid tail, ISSUE 8) ----------------------
# Forced-on windowed handoff at a small n — the host-side window split at
# W=4, the accelerator window queue (device hi-sort + slice stream)
# forced on cpu, and the resumable fold — each bit-identical to the
# oracle.  Seconds of work; a regression in the round-7 streaming tail
# fails the gate before pytest even runs.
if ! env JAX_PLATFORMS=cpu SHEEP_HANDOFF_WINDOWS=4 \
     python - <<'EOF'
import os
import numpy as np
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.ops import build_graph_hybrid
from sheep_tpu.utils.synth import rmat_edges

n = 1 << 12
tail, head = rmat_edges(12, 4 * n, seed=31)
want_seq = degree_sequence(tail, head)
want = build_forest(tail, head, want_seq)

perf = {}
seq, forest = build_graph_hybrid(tail, head, n, perf=perf)
assert perf.get("stream_mode") == "windowed", perf
np.testing.assert_array_equal(seq, want_seq)
np.testing.assert_array_equal(forest.parent, want.parent)
np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)

# accelerator transfer machinery (window queue) forced on cpu
os.environ["SHEEP_STREAM_DEVICE_WINDOWS"] = "1"
os.environ["SHEEP_OVERLAP_SLICE"] = "16384"
perf2 = {}
seq2, forest2 = build_graph_hybrid(tail, head, n, perf=perf2)
assert perf2.get("stream_mode") == "windowed", perf2
np.testing.assert_array_equal(forest2.parent, want.parent)
np.testing.assert_array_equal(forest2.pst_weight, want.pst_weight)
EOF
then
  echo "STREAM-HANDOFF SMOKE FAILED: windowed handoff diverged from the" \
       "oracle" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- out-of-core smoke (external-memory build, ISSUE 9) ------------------
# A tiny SHEEP_MEM_BUDGET under which the governor-planned ladder skips
# host AND stream but keeps the ext rung (rss reading zeroed so the plan
# is deterministic), oracle-checked bit-identical; plus a forced
# EIO-at-block arm that must retry mid-stream to the same tree.  Seconds
# of work; a regression in the round-8 out-of-core path fails the gate
# before pytest even runs.
if ! env JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np, tempfile
import sheep_tpu.resources.governor as G
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.io import faultfs
from sheep_tpu.io.edges import write_dat
from sheep_tpu.ops.extmem import build_forest_extmem
from sheep_tpu.runtime import RuntimeConfig, build_graph_resilient
from sheep_tpu.utils.synth import rmat_edges

d = tempfile.mkdtemp()
tail, head = rmat_edges(14, 1 << 18, seed=41)
p = d + "/g.dat"
write_dat(p, tail, head)
want_seq = degree_sequence(tail, head)
want = build_forest(tail, head, want_seq)
n, links = len(want_seq), len(tail)

G.rss_bytes = lambda: 0  # deterministic headroom for the plan
gov = G.ResourceGovernor(mem_budget=1)
ext_est = G.rung_peak_nbytes("ext", n, links,
                             ext_block=gov.ext_fitted_block(n))
stream_est = G.rung_peak_nbytes("stream", n, links)
assert ext_est < stream_est, (ext_est, stream_est)
budget = (ext_est + stream_est) // 2
cfg = RuntimeConfig(governor=G.ResourceGovernor(mem_budget=budget),
                    edges_path=p)
seq, f = build_graph_resilient(tail, head, config=cfg)
skipped = {e[1] for e in cfg.events if e[0] == "mem-skip-rung"}
assert "stream" in skipped and "host" in skipped, cfg.events
assert any(e[0] == "ext-block" for e in cfg.events), "ext rung never ran"
np.testing.assert_array_equal(seq, want_seq)
np.testing.assert_array_equal(f.parent, want.parent)
np.testing.assert_array_equal(f.pst_weight, want.pst_weight)

# forced EIO at the 2nd block read: in-process retry, bit-identical
faultfs.install_plan(faultfs.parse_io_fault_plan("eio@dat:1"))
perf = {}
seq2, f2 = build_forest_extmem(p, block_edges=1 << 15,
                               backoff_base_s=0.0, perf=perf)
faultfs.clear_plan()
assert perf["retries"] + perf.get("seq_retries", 0) == 1, perf
np.testing.assert_array_equal(seq2, want_seq)
np.testing.assert_array_equal(f2.parent, want.parent)
np.testing.assert_array_equal(f2.pst_weight, want.pst_weight)
EOF
then
  echo "OUT-OF-CORE SMOKE FAILED: the ext rung diverged from the oracle" \
       "or did not survive its reader fault" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- distext smoke (distributed out-of-core build, ISSUE 13) -------------
# A 2-leg supervised build of a synthetic .dat >= 4x over each leg's
# SHEEP_MEM_BUDGET: per-range histograms Allreduce into the shared
# sequence, per-leg ext folds tournament-merge — oracle-bit-identical
# tree CRC vs BOTH the single-host ext arm and the in-RAM oracle; then a
# kill of one leg mid-range whose recovery re-dispatches ONLY that leg
# (resuming its own block checkpoint); the state dir (.hist artifacts +
# shard-map chain) must fsck clean.  Seconds of work (in-process legs);
# a regression anywhere in the distext composition fails the gate before
# pytest even runs.
DISTEXT_DIR=$(mktemp -d)
if env JAX_PLATFORMS=cpu SHEEP_MEM_BUDGET=768K python - "$DISTEXT_DIR" <<'EOF'
import os, sys, zlib
import numpy as np
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.io.edges import write_dat
from sheep_tpu.io.trefile import read_tree
from sheep_tpu.ops.distext import run_distext
from sheep_tpu.ops.extmem import build_forest_extmem
from sheep_tpu.runtime import FaultPlan, clear_plan, install_plan, reset_counters
from sheep_tpu.supervisor import InlineRunner, SupervisorConfig
from sheep_tpu.utils.synth import rmat_edges

d = sys.argv[1]
tail, head = rmat_edges(14, 1 << 18, seed=61)
p = d + "/g.dat"
write_dat(p, tail, head)
budget = 768 << 10
assert os.path.getsize(p) >= 4 * budget, "file must be >= 4x the leg budget"
want = build_forest(tail, head, degree_sequence(tail, head))
crc = lambda f: (zlib.crc32(np.asarray(f[0]).tobytes()),
                 zlib.crc32(np.asarray(f[1]).tobytes()))
oracle_crc = crc((want.parent, want.pst_weight))
_, ext_f = build_forest_extmem(p)   # the single-host ext arm
assert crc((ext_f.parent, ext_f.pst_weight)) == oracle_crc

def run(name, **kw):
    cfg = SupervisorConfig(poll_s=0.01, backoff_base_s=0.0, grammar=False, **kw)
    m = run_distext(p, f"{d}/{name}", cfg, runner=InlineRunner(0.05), legs=2)
    return crc(read_tree(m.final_tree)), m

base_crc, _ = run("base")
assert base_crc == oracle_crc, "distext diverged from the oracle/ext CRC"

# kill one leg mid-range at a block boundary: the re-dispatch resumes the
# leg's own checkpoint and ONLY that leg runs twice
reset_counters()
install_plan(FaultPlan(site="ext-boundary", at=1, kind="kill"))
hurt_crc, m = run("legkill", cores=1)
clear_plan()
assert hurt_crc == oracle_crc, "killed-leg recovery diverged"
counts = {leg.key: leg.dispatches for leg in m.legs}
assert counts["r0.00"] == 2, counts
assert all(n == 1 for k, n in counts.items() if k != "r0.00"), counts
EOF
then
  if ! env JAX_PLATFORMS=cpu bin/fsck -q "$DISTEXT_DIR/base" > /dev/null
  then
    echo "DISTEXT SMOKE FAILED: the state dir (.hist artifacts or the" \
         "shard-map chain) did not fsck clean" >&2
    rm -rf "$DISTEXT_DIR"; exit 1
  fi
  rm -rf "$DISTEXT_DIR"
else
  echo "DISTEXT SMOKE FAILED: 2-leg distributed build diverged from the" \
       "oracle or re-dispatched more than the killed leg" >&2
  rm -rf "$DISTEXT_DIR"; exit 1
fi
# -------------------------------------------------------------------------

# --- multi-host smoke (remote build workers, ISSUE 16) -------------------
# Two real bin/worker subprocess daemons on loopback with SEPARATE state
# dirs (nothing shared but the wire): a shipped 2-leg distext build must
# be CRC-identical to the single-host ext arm and the in-RAM oracle with
# every dispatch count exactly 1; then kill -9 one worker mid-leg (a
# watcher fires the moment its first slice lands) and assert the
# supervisor re-dispatches EXACTLY one leg to the survivor, tree still
# CRC-identical.  Seconds of work (the worker stack imports no jax); a
# regression anywhere in the remote-dispatch/recovery path fails the
# gate before pytest even runs.
MHOST_DIR=$(mktemp -d)
if env JAX_PLATFORMS=cpu SHEEP_WORKER_TRANSPORT=ship \
    python - "$MHOST_DIR" <<'EOF'
import glob, os, signal, subprocess, sys, threading, time, zlib
REPO = os.getcwd()
sys.path.insert(0, REPO)
import numpy as np
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.io.edges import write_dat
from sheep_tpu.io.trefile import read_tree
from sheep_tpu.ops.distext import run_distext
from sheep_tpu.ops.extmem import build_forest_extmem
from sheep_tpu.serve.worker import read_worker_addr
from sheep_tpu.supervisor import InlineRunner, SupervisorConfig
from sheep_tpu.utils.synth import rmat_edges

d = sys.argv[1]
tail, head = rmat_edges(14, 1 << 18, seed=67)
p = d + "/g.dat"
write_dat(p, tail, head)
want = build_forest(tail, head, degree_sequence(tail, head))
crc = lambda f: (zlib.crc32(np.asarray(f[0]).tobytes()),
                 zlib.crc32(np.asarray(f[1]).tobytes()))
oracle_crc = crc((want.parent, want.pst_weight))
_, ext_f = build_forest_extmem(p)   # the single-host ext arm
assert crc((ext_f.parent, ext_f.pst_weight)) == oracle_crc

env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["SHEEP_MEM_BUDGET"] = "768K"   # each worker's OWN budget

def spawn_worker(wd):
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.cli.worker", "-d", wd],
        env=env, cwd=REPO)

def waddr(wd, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            return read_worker_addr(wd)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"{wd}/worker.addr never appeared")

def run(name, addrs):
    cfg = SupervisorConfig(poll_s=0.01, backoff_base_s=0.0,
                           grammar=False, worker_addrs=list(addrs),
                           worker_beat_s=0.1)
    m = run_distext(p, f"{d}/{name}", cfg, runner=InlineRunner(0.05),
                    legs=2)
    return crc(read_tree(m.final_tree)), m

# two worker daemons, separate state dirs, nothing shared but the wire
w1d, w2d = d + "/w1", d + "/w2"
procs = [spawn_worker(w1d), spawn_worker(w2d)]
base_crc, m = run("base", [waddr(w1d), waddr(w2d)])
assert base_crc == oracle_crc, "remote build diverged from the ext CRC"
counts = {leg.key: leg.dispatches for leg in m.legs}
assert all(n == 1 for n in counts.values()), counts
shipped = glob.glob(w1d + "/*.slice.dat") + glob.glob(w2d + "/*.slice.dat")
assert shipped, "no leg was actually shipped over the wire"

# kill -9 one worker the moment its first shipped slice lands: the
# supervisor must re-dispatch EXACTLY that one leg to the survivor
w3d, w4d = d + "/w3", d + "/w4"
procs += [spawn_worker(w3d), spawn_worker(w4d)]
victim = procs[2]
addrs2 = [waddr(w3d), waddr(w4d)]

def killer():
    while victim.poll() is None:
        if glob.glob(w3d + "/*.slice.dat"):
            victim.send_signal(signal.SIGKILL)
            return
        time.sleep(0.002)

t = threading.Thread(target=killer, daemon=True)
t.start()
hurt_crc, m = run("hurt", addrs2)
t.join(timeout=10)
assert victim.poll() is not None, "the victim worker was never killed"
assert hurt_crc == oracle_crc, "killed-worker recovery diverged"
counts = sorted(leg.dispatches for leg in m.legs)
assert counts == [1] * (len(counts) - 1) + [2], counts

for pr in procs:
    if pr.poll() is None:
        pr.send_signal(signal.SIGTERM)
        pr.wait(timeout=60)
EOF
then
  rm -rf "$MHOST_DIR"
else
  echo "MULTI-HOST SMOKE FAILED: remote-worker build diverged from the" \
       "oracle or kill -9 did not re-dispatch exactly one leg" >&2
  rm -rf "$MHOST_DIR"; exit 1
fi
# -------------------------------------------------------------------------

# --- deterministic-plan smoke (the planner, ISSUE 15) --------------------
# `sheep plan --explain` on a small .dat under a budget: the output must
# name the chosen rung, and — with the measured-RSS input pinned
# (--assume-rss 0) — the same inputs must print byte-identical plans
# twice.  Seconds of work; a nondeterministic or broken planner fails
# the gate before pytest even runs.
PLAN_DIR=$(mktemp -d)
if env JAX_PLATFORMS=cpu python - "$PLAN_DIR" <<'EOF'
import sys
from sheep_tpu.io.edges import write_dat
from sheep_tpu.utils.synth import rmat_edges
tail, head = rmat_edges(12, 1 << 14, seed=7)
write_dat(sys.argv[1] + "/g.dat", tail, head)
EOF
then
  if ! env JAX_PLATFORMS=cpu SHEEP_MEM_BUDGET=64M \
      bin/plan --explain --assume-rss 0 "$PLAN_DIR/g.dat" \
      > "$PLAN_DIR/plan1.txt"; then
    echo "PLAN SMOKE FAILED: sheep plan --explain did not run" >&2
    rm -rf "$PLAN_DIR"; exit 1
  fi
  if ! grep -q "chosen rung:" "$PLAN_DIR/plan1.txt"; then
    echo "PLAN SMOKE FAILED: the plan did not name a chosen rung" >&2
    cat "$PLAN_DIR/plan1.txt" >&2
    rm -rf "$PLAN_DIR"; exit 1
  fi
  env JAX_PLATFORMS=cpu SHEEP_MEM_BUDGET=64M \
      bin/plan --explain --assume-rss 0 "$PLAN_DIR/g.dat" \
      > "$PLAN_DIR/plan2.txt"
  if ! cmp -s "$PLAN_DIR/plan1.txt" "$PLAN_DIR/plan2.txt"; then
    echo "PLAN SMOKE FAILED: the same inputs yielded two different" \
         "plans" >&2
    diff "$PLAN_DIR/plan1.txt" "$PLAN_DIR/plan2.txt" >&2
    rm -rf "$PLAN_DIR"; exit 1
  fi
  rm -rf "$PLAN_DIR"
else
  echo "PLAN SMOKE FAILED: could not write the probe graph" >&2
  rm -rf "$PLAN_DIR"; exit 1
fi
# -------------------------------------------------------------------------

# --- hep-th ECV(down) regression gate (quality matrix, first slice) ------
# Build the bundled hep-th graph, partition the degree-sequence tree for
# every published part count, and assert ECV(down) <= the recorded
# baseline (data/hepth-ecv-baseline.json — the reference's published
# sweep): a quality regression anywhere in sequence/build/partition
# fails the gate before pytest even runs; an improvement passes.
if ! env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.io import load_edges
from sheep_tpu.partition.evaluate import evaluate_partition
from sheep_tpu.partition.partition import Partition

base = json.load(open("data/hepth-ecv-baseline.json"))["ecv_down"]
e = load_edges("data/hep-th.dat")
seq = degree_sequence(e.tail, e.head)
forest = build_forest(e.tail, e.head, seq)
for p_s, ceiling in sorted(base.items(), key=lambda kv: int(kv[0])):
    p = int(p_s)
    part = Partition.from_forest(seq, forest, p, max_vid=e.max_vid)
    rep = evaluate_partition(part.parts, e.tail, e.head, seq, p,
                             max_vid=e.max_vid, file_edges=e.num_edges)
    assert rep.ecv_down <= ceiling, (
        f"hep-th ECV(down) regressed at p={p}: {rep.ecv_down} > "
        f"baseline {ceiling}")
    print(f"hep-th p={p}: ECV(down) {rep.ecv_down} <= {ceiling}")
EOF
then
  echo "HEP-TH ECV GATE FAILED: partition quality regressed past the" \
       "recorded baseline" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- power-law ECV(down) regression gate (quality matrix, 2nd family) ----
# The second graph family (ISSUE 20 satellite): a deterministic RMAT
# synthesis — the skewed power-law degree tail the degree sequence is
# built to exploit — partitioned for every baselined part count and
# held to data/powerlaw-ecv-baseline.json.  hep-th alone gates one
# degree distribution; a sequence/build/partition change that only
# hurts heavy-tailed graphs now fails here instead of shipping.
if ! env JAX_PLATFORMS=cpu python - <<'EOF'
import json
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.partition.evaluate import evaluate_partition
from sheep_tpu.partition.partition import Partition
from sheep_tpu.utils.synth import rmat_edges

spec = json.load(open("data/powerlaw-ecv-baseline.json"))
gen, base = spec["generator"], spec["ecv_down"]
tail, head = rmat_edges(gen["log2_nodes"], gen["edges"],
                        seed=gen["seed"])
max_vid = int(max(tail.max(), head.max()))
seq = degree_sequence(tail, head)
forest = build_forest(tail, head, seq)
for p_s, ceiling in sorted(base.items(), key=lambda kv: int(kv[0])):
    p = int(p_s)
    part = Partition.from_forest(seq, forest, p, max_vid=max_vid)
    rep = evaluate_partition(part.parts, tail, head, seq, p,
                             max_vid=max_vid, file_edges=len(tail))
    assert rep.ecv_down <= ceiling, (
        f"power-law ECV(down) regressed at p={p}: {rep.ecv_down} > "
        f"baseline {ceiling}")
    print(f"power-law p={p}: ECV(down) {rep.ecv_down} <= {ceiling}")
EOF
then
  echo "POWER-LAW ECV GATE FAILED: partition quality regressed past" \
       "the recorded baseline on the heavy-tailed family" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- flight-recorder smoke (observability, ISSUE 10) ---------------------
# One traced build (SHEEP_TRACE on): the tree must stay oracle-exact, the
# trace file must fsck clean (sealed sidecar + parseable JSONL), and
# `sheep trace` must render its rollup + rung explanation with exit 0.
# Seconds of work; a regression anywhere in the obs layer fails the gate
# before pytest even runs.
OBS_DIR=$(mktemp -d)
if env JAX_PLATFORMS=cpu SHEEP_TRACE="$OBS_DIR/build.trace" python - <<'EOF'
import numpy as np
from sheep_tpu.core import build_forest, degree_sequence
from sheep_tpu.runtime import RuntimeConfig, build_graph_resilient
from sheep_tpu.utils.synth import rmat_edges

tail, head = rmat_edges(10, 4 << 10, seed=19)
want = build_forest(tail, head, degree_sequence(tail, head))
seq, forest = build_graph_resilient(
    tail, head, config=RuntimeConfig(ladder=("single", "host")))
np.testing.assert_array_equal(forest.parent, want.parent)
np.testing.assert_array_equal(forest.pst_weight, want.pst_weight)
EOF
then
  if ! env JAX_PLATFORMS=cpu bin/fsck -q "$OBS_DIR/build.trace" \
      > /dev/null; then
    echo "OBS SMOKE FAILED: traced build left a trace that fails fsck" >&2
    rm -rf "$OBS_DIR"; exit 1
  fi
  if ! env JAX_PLATFORMS=cpu bin/trace "$OBS_DIR/build.trace" \
      | grep -q "ran: rung"; then
    echo "OBS SMOKE FAILED: sheep trace did not explain the ladder rung" >&2
    rm -rf "$OBS_DIR"; exit 1
  fi
  rm -rf "$OBS_DIR"
else
  echo "OBS SMOKE FAILED: the traced build diverged from the oracle" >&2
  rm -rf "$OBS_DIR"; exit 1
fi
# -------------------------------------------------------------------------

# --- serve smoke (crash-safe partition service, ISSUE 6) -----------------
# Start a real bin/serve subprocess on a tiny graph, query + insert over
# the wire, kill -9, restart from the same state dir, and assert the
# recovered daemon serves the same answers with every acknowledged
# insert intact.  Seconds of work (the serve stack imports no jax); a
# regression in the WAL/snapshot recovery path fails the gate before
# pytest even runs.
if ! python - <<'EOF'
import os, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
import numpy as np
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=23)
write_dat(work + "/g.dat", tail, head)
state = work + "/state"
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

def addr(timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(state + "/serve.addr").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit("serve.addr never appeared")

proc = subprocess.Popen(
    [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", state,
     "-g", work + "/g.dat", "-k", "3"], env=env, cwd=REPO)
c = connect_retry(*addr(), timeout_s=60)
for i in range(5):
    c.insert([(int(tail[i]), int(head[(i + 7) % len(head)]))])
post_parts = c.part(list(range(100)))
st = c.kv("STATS")
assert st["applied_seqno"] == 5, st
c.close()
proc.send_signal(signal.SIGKILL)   # kill -9: no flush, no goodbye
proc.wait(timeout=60)
os.unlink(state + "/serve.addr")
proc = subprocess.Popen(
    [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", state],
    env=env, cwd=REPO)
c = connect_retry(*addr(), timeout_s=60)
st = c.kv("STATS")
assert st["applied_seqno"] == 5, ("acked insert lost across kill -9", st)
assert c.part(list(range(100))) == post_parts, "recovered parts diverged"
# METRICS scrape (ISSUE 10): Prometheus grammar over the wire, per-verb
# counters live, and STATS quantiles derived from the same registry
body = c.metrics()
assert "# TYPE sheep_serve_requests_total counter" in body, body[:400]
assert 'sheep_serve_requests_total{verb="PART"}' in body, body[:400]
assert "# TYPE sheep_serve_request_seconds histogram" in body
assert "sheep_serve_applied_seqno 5" in body, body[:400]
st = c.kv("STATS")
assert st["req_part"] >= 1 and float(st["p99_part_ms"]) > 0, st
c.request("QUIT")
c.close()
proc.send_signal(signal.SIGTERM)
proc.wait(timeout=60)
EOF
then
  echo "SERVE SMOKE FAILED: kill -9 recovery did not reproduce the" \
       "pre-crash serving state" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- failover smoke (replicated serve, ISSUE 7) --------------------------
# A real 2-node cluster of bin/serve subprocesses: wire-bootstrapped
# follower, synchronously-replicated inserts, kill -9 the leader, assert
# the follower promotes (epoch bumped) with ZERO acked inserts lost and
# identical answers, then the fenced ex-leader rejoins as a follower and
# write availability returns.  Seconds of work; a regression anywhere in
# the replication/failover stack fails the gate before pytest even runs.
if ! python - <<'EOF'
import os, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeClient, connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=29)
write_dat(work + "/g.dat", tail, head)
lead_d, fol_d = work + "/lead", work + "/fol"
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["SHEEP_SERVE_REPL_HB_S"] = "0.1"
env["SHEEP_SERVE_FAILOVER_S"] = "1"

def addr(d, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(d + "/serve.addr").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"{d}/serve.addr never appeared")

def spawn(d, *args):
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", d, *args],
        env=env, cwd=REPO)

lead = spawn(lead_d, "-g", work + "/g.dat", "-k", "3",
             "--role", "leader", "--node-id", "lead", "--peers", fol_d)
lh, lp = addr(lead_d)
fol = spawn(fol_d, "--role", "follower", "--node-id", "fol",
            "--peers", lead_d)
c = connect_retry(lh, lp, timeout_s=60)
deadline = time.monotonic() + 60
while c.kv("STATS").get("followers", 0) < 1:
    assert time.monotonic() < deadline, "follower never attached"
    time.sleep(0.1)
for i in range(5):  # every OK = leader fsync + follower ack
    c.insert([(int(tail[i]), int(head[(i + 3) % len(head)]))])
pre_parts = c.part(list(range(100)))
assert c.kv("STATS")["applied_seqno"] == 5
c.close()
lead.send_signal(signal.SIGKILL)   # kill -9: no flush, no goodbye
lead.wait(timeout=60)
os.unlink(lead_d + "/serve.addr")

fc = connect_retry(*addr(fol_d), timeout_s=60)
deadline = time.monotonic() + 60
while fc.kv("STATS").get("role") != "leader":
    assert time.monotonic() < deadline, "follower never promoted"
    time.sleep(0.1)
st = fc.kv("STATS")
assert st["applied_seqno"] == 5, ("acked insert lost across failover", st)
assert st["epoch"] == 1, ("promotion must bump the epoch", st)
assert fc.part(list(range(100))) == pre_parts, "promoted parts diverged"

# fenced ex-leader rejoins: demotes, catches up, restores write quorum
ex = spawn(lead_d, "--role", "leader", "--node-id", "lead",
           "--peers", fol_d)
deadline = time.monotonic() + 60
while fc.kv("STATS").get("followers", 0) < 1:
    assert time.monotonic() < deadline, "ex-leader never rejoined"
    time.sleep(0.1)
fc.insert([(int(tail[7]), int(head[2]))])  # write availability is back
assert fc.kv("STATS")["applied_seqno"] == 6
ec = connect_retry(*addr(lead_d), timeout_s=60)
st = ec.kv("STATS")
assert st["role"] == "follower", ("ex-leader split-brained", st)
ec.request("QUIT"); ec.close()
fc.request("QUIT"); fc.close()
for p in (ex, fol):
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=60)
EOF
then
  echo "FAILOVER SMOKE FAILED: leader kill -9 did not promote a" \
       "lossless fenced follower" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- fleet smoke (multi-tenant serving + router + observatory, ISSUES 11/12)
# A replicated cluster hosting 2 tenants behind a bin/route process, each
# process flight-recorded (SHEEP_TRACE): route queries+inserts to BOTH
# tenants, kill -9 the backing leader, assert failover-through-router
# with zero acked-insert loss, restore write quorum via the rejoined
# ex-leader — then assert the OBSERVATORY: `sheep trace --merge` stitches
# ONE rid across router + dead leader + promoted follower, the router's
# fleet scrape carries per-instance/cluster labels + derived gauges, and
# `sheep top --json` renders the per-tenant table.  Seconds of work; a
# regression anywhere in the tenant/router/observatory stack fails the
# gate before pytest even runs.
if ! python - <<'EOF'
import json, os, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeError, connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=31)
write_dat(work + "/g.dat", tail, head)
lead_d, fol_d, route_d = work + "/lead", work + "/fol", work + "/route"
tdir = work + "/tr"
os.makedirs(tdir)
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["SHEEP_SERVE_REPL_HB_S"] = "0.1"
env["SHEEP_SERVE_FAILOVER_S"] = "1"

def addr(d, name="serve.addr", timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(f"{d}/{name}").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"{d}/{name} never appeared")

def spawn(mod, d, *args, trace=None):
    e = dict(env)
    if trace:
        e["SHEEP_TRACE"] = f"{tdir}/{trace}.trace"
    return subprocess.Popen(
        [sys.executable, "-m", mod, "-d", d, *args], env=e, cwd=REPO)

lead = spawn("sheep_tpu.cli.serve", lead_d, "-g", work + "/g.dat",
             "-k", "3", "--role", "leader", "--node-id", "lead",
             "--peers", fol_d, "--tenant",
             f"web={work}/lead-web:{work}/g.dat:3", trace="lead")
addr(lead_d)
fol = spawn("sheep_tpu.cli.serve", fol_d, "--role", "follower",
            "--node-id", "fol", "--peers", lead_d,
            "--tenant", f"web={work}/fol-web", trace="fol")
addr(fol_d)
router = spawn("sheep_tpu.cli.route", route_d,
               "--cluster", f"{lead_d},{fol_d}", trace="router")
rh, rp = addr(route_d, name="router.addr")
c = connect_retry(rh, rp, timeout_s=60)
# both tenants reachable and streaming before the kill
deadline = time.monotonic() + 60
acked = {"default": 0, "web": 0}
while time.monotonic() < deadline:
    try:
        c.tenant("web")
        if c.kv("STATS").get("followers") == 1:
            break
    except ServeError:
        pass
    time.sleep(0.1)
for t in ("default", "web"):
    c.tenant(t)
    for i in range(3):  # every OK = leader fsync + follower ack
        c.insert([(int(tail[i]), int(head[(i + 5) % len(head)]))])
        acked[t] += 1
parts = {}
for t in ("default", "web"):
    c.tenant(t)
    parts[t] = c.part(list(range(100)))
    assert c.kv("STATS")["applied_seqno"] == acked[t]

lead.send_signal(signal.SIGKILL)   # kill -9 the backing leader
lead.wait(timeout=60)
os.unlink(lead_d + "/serve.addr")
# failover THROUGH the router: the promoted follower answers for both
# tenants with zero acked-insert loss and identical parts
deadline = time.monotonic() + 60
promoted = None
while promoted is None and time.monotonic() < deadline:
    try:
        c.tenant("default")
        st = c.kv("STATS")
        if st.get("role") == "leader" and st.get("epoch", 0) >= 1:
            promoted = st
    except (ServeError, ConnectionError, OSError):
        time.sleep(0.1)
assert promoted is not None, "failover never surfaced via router"
for t in ("default", "web"):
    c.tenant(t)
    st = c.kv("STATS")
    assert st["applied_seqno"] == acked[t], ("acked loss", t, st)
    assert c.part(list(range(100))) == parts[t], f"{t} parts diverged"
# rejoined ex-leader restores the write quorum, through the router
ex = spawn("sheep_tpu.cli.serve", lead_d, "--role", "leader",
           "--node-id", "lead", "--peers", fol_d,
           "--tenant", f"web={work}/lead-web")
addr(lead_d)
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    c.tenant("web")
    if c.kv("STATS").get("followers") == 1:
        break
    time.sleep(0.1)
c.insert([(int(tail[7]), int(head[2]))])
assert c.kv("STATS")["applied_seqno"] == acked["web"] + 1

# --- the observatory half (ISSUE 12) ---
# (1) the router's METRICS is now the FLEET scrape: per-member series
# carry instance/cluster labels, tenant labels ride through, the
# derived fleet gauges and process self-accounting are present
from sheep_tpu.obs.metrics import parse_prometheus
body = c.metrics()
samples = parse_prometheus(body)
def find(name, **want):
    return [v for n, lb, v in samples if n == name
            and all(lb.get(k) == w for k, w in want.items())]
insts = {lb["instance"] for n, lb, v in samples
         if n == "sheep_serve_epoch" and "instance" in lb}
assert len(insts) >= 2, f"fleet scrape labeled {insts} instances"
assert all(lb.get("cluster") == "c0" for n, lb, v in samples
           if n == "sheep_serve_epoch" and "instance" in lb)
assert find("sheep_serve_tenant_resident", tenant="web") != []
assert find("sheep_fleet_members_reachable", cluster="c0")[0] >= 2
assert find("sheep_fleet_tenant_resident_instances", tenant="web")
assert find("sheep_process_vmrss_bytes") != []
assert any(n == "sheep_serve_tenant_requests_total"
           and lb.get("tenant") == "web" for n, lb, v in samples)
# (2) sheep top --json renders the per-tenant table from that scrape
top = subprocess.run(
    [sys.executable, "-m", "sheep_tpu.cli.top", "-r", f"{rh}:{rp}",
     "--json", "-i", "0"], env=env, cwd=REPO, capture_output=True)
assert top.returncode == 0, top.stderr[:400]
view = json.loads(top.stdout)
assert "web" in view["tenants"], view["tenants"].keys()
assert view["tenants"]["web"]["resident"] >= 1
c.request("QUIT")
c.close()
# (3) the merged timeline: one rid spanning router + the DEAD leader +
# the promoted follower (a pre-kill quorum-acked insert crossed all
# three; the dead leader's trace has a legal torn tail)
from sheep_tpu.obs.merge import (collect_trace_paths, estimate_offsets,
                                 load_sources, merge_by_rid)
sources = load_sources(collect_trace_paths([tdir]))
assert len(sources) == 3, [s.path for s in sources]
estimate_offsets(sources)
rids = merge_by_rid(sources)
spanning = {rid: {r["_src"] for r in recs} for rid, recs in rids.items()}
full = [rid for rid, srcs in spanning.items()
        if {"router", "lead", "fol"} <= srcs]
assert full, f"no rid spans router+lead+fol: {spanning}"
fol_names = {r["name"] for r in rids[full[0]] if r["_src"] == "fol"}
assert "wal.fsync" in fol_names, fol_names  # the follower-side fsync
# the CLI renders the same merge (exit 0, the rid in the output)
mg = subprocess.run(
    [sys.executable, "-m", "sheep_tpu.cli.trace", "--merge",
     "--rid", full[0], tdir], env=env, cwd=REPO, capture_output=True)
assert mg.returncode == 0, mg.stderr[:400]
assert full[0] in mg.stdout.decode(), mg.stdout[:400]
for p in (router, ex, fol):
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=60)
EOF
then
  echo "FLEET SMOKE FAILED: 2-tenant router failover lost acked inserts," \
       "per-tenant metrics, or the merged rid timeline" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- migrate smoke (live tenant migration, ISSUE 17) ---------------------
# Two real single-node clusters behind a bin/route process: adopt the
# tenant on the target (phase 1 snapshot + phase 2 delta stream live),
# kill -9 the SOURCE leader mid-delta, restart it on the same state dir,
# then drive the routed MIGRATE to completion — the driver must re-pin
# the delta stream to the restarted leader, cut over epoch-fenced, and
# leave a CRC-equal tenant tree on the target with every acked insert
# applied exactly once and the source answering typed `ERR moved`.
# Seconds of work; a regression anywhere in the migration path fails the
# gate before pytest even runs.
if ! python - <<'EOF'
import os, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeClient, ServeError, connect_retry
from sheep_tpu.serve.router import HashRing
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=37)
write_dat(work + "/g.dat", tail, head)
ring = HashRing(["c0", "c1"])
src = ring.lookup("hot")
dst = "c1" if src == "c0" else "c0"
dirs = {cid: f"{work}/{cid}" for cid in ("c0", "c1")}
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

def addr(d, name="serve.addr", timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(f"{d}/{name}").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"{d}/{name} never appeared")

def spawn(d, *args):
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", d, *args],
        env=env, cwd=REPO)

procs = {}
for cid in ("c0", "c1"):
    flags = ["--tenant", f"hot={work}/{cid}-hot:{work}/g.dat:3"] \
        if cid == src else []
    procs[cid] = spawn(dirs[cid], "-g", work + "/g.dat", "-k", "3",
                       *flags)
    addr(dirs[cid])
router = subprocess.Popen(
    [sys.executable, "-m", "sheep_tpu.cli.route", "-d", work + "/route",
     "--cluster", f"c0@{dirs['c0']}", "--cluster", f"c1@{dirs['c1']}"],
    env=env, cwd=REPO)
rh, rp = addr(work + "/route", name="router.addr")
c = connect_retry(rh, rp, timeout_s=60)
deadline = time.monotonic() + 60
while True:  # the spec'd tenant answers through the router
    try:
        c.tenant("hot")
        c.kv("STATS")
        break
    except ServeError:
        assert time.monotonic() < deadline, "tenant never came up"
        time.sleep(0.1)
acked = 0
for i in range(8):
    c.insert([(int(tail[i]), int(head[(i + 5) % len(head)]))])
    acked += 1

# phase 1+2 by hand: adopt on the target, wait for the live delta
# stream, then kill -9 the source mid-delta
sh, sp = addr(dirs[src])
with ServeClient(*addr(dirs[dst]), timeout_s=60) as tc:
    rec = tc.kv(f"MIG ADOPT hot host={sh} port={sp}")
    assert rec["phase"] in ("snap", "delta"), rec
    deadline = time.monotonic() + 60
    while int(tc.kv("MIG STAT hot")["applied"]) < acked:
        assert time.monotonic() < deadline, "delta stream never drained"
        time.sleep(0.05)
procs[src].send_signal(signal.SIGKILL)   # kill -9: no flush, no goodbye
procs[src].wait(timeout=60)
os.unlink(dirs[src] + "/serve.addr")
procs[src] = spawn(dirs[src], "--tenant", f"hot={work}/{src}-hot")
addr(dirs[src])

# the routed MIGRATE resumes: re-pins the stream to the restarted
# leader, drains, cuts over epoch-fenced
rec = c.kv(f"MIGRATE hot {dst} wait=120")
assert rec["phase"] == "done", rec

# CRC-equal tenant tree, exact applied count, typed moved on the source
with ServeClient(*addr(dirs[dst]), timeout_s=60) as tc:
    tstat = tc.kv("MIG STAT hot")
with ServeClient(*addr(dirs[src]), timeout_s=60) as sc:
    sstat = sc.kv("MIG STAT hot")
    assert sstat["phase"] == "moved", sstat
    try:
        sc.tenant("hot")
        sc.insert([(0, 1)])
        raise SystemExit("fenced source accepted an INSERT")
    except ServeError as exc:
        assert exc.code == "moved" and f"dest={dst}" in exc.detail, exc
assert tstat["crc"] == sstat["crc"], (tstat, sstat)
assert int(tstat["applied"]) == acked, (tstat, acked)
assert int(tstat["epoch"]) > int(sstat["epoch"]), (tstat, sstat)
c.insert([(int(tail[9]), int(head[1]))])  # routed write on the new home
acked += 1
with ServeClient(*addr(dirs[dst]), timeout_s=60) as tc:
    assert int(tc.kv("MIG STAT hot")["applied"]) == acked
c.request("QUIT")
c.close()
router.send_signal(signal.SIGTERM)
router.wait(timeout=60)
for p in procs.values():
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=60)
EOF
then
  echo "MIGRATE SMOKE FAILED: kill -9 of the source mid-delta did not" \
       "resume to an epoch-fenced CRC-equal cutover" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- reseq smoke (crash-safe re-sequencing, ISSUE 18) --------------------
# A real bin/serve daemon under a sustained power-law insert stream: the
# sequence-drift detector trips the background re-sequence on its own,
# an injected kill -9 (os._exit(137), no flush, no goodbye) lands at the
# fold phase, and the RESTARTED daemon resumes the rebuild from its
# durable manifest — finishing on generation 1 with a serving-state CRC
# equal to a cold offline rebuild from the same durable bytes.
if ! python - <<'EOF'
import os, shutil, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
import numpy as np
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeClient, connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=41)
write_dat(work + "/g.dat", tail, head)
sd = work + "/state"
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["JAX_PLATFORMS"] = "cpu"
env["SHEEP_RESEQ_DRIFT_MIN"] = "32"
env["SHEEP_RESEQ_DRIFT"] = "0.25"
env["SHEEP_RESEQ_PIN"] = "go"

def addr(timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(sd + "/serve.addr").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit("serve.addr never appeared")

def spawn(*args, fault=None):
    e = dict(env)
    if fault:
        e["SHEEP_SERVE_FAULT_PLAN"] = fault
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", sd,
         *args], env=e, cwd=REPO)

# sustained skewed inserts trip the detector; the armed kill lands at
# the fold phase of the background re-sequence
p = spawn("-g", work + "/g.dat", "-k", "3",
          fault="kill@reseq-fold:0")
c = connect_retry(*addr(), timeout_s=60)
rng = np.random.default_rng(5)
i = 0
deadline = time.monotonic() + 90
while p.poll() is None:
    assert time.monotonic() < deadline, "kill@reseq-fold never fired"
    try:
        u = 200 + int(rng.integers(0, 6))
        c.insert([(u, int(tail[i % len(tail)]))])
        i += 1
    except Exception:
        break  # the daemon died mid-request: exactly the point
p.wait(timeout=60)
assert p.returncode == 137, f"want kill -9 exit, got {p.returncode}"
from sheep_tpu.serve import reseq
assert reseq.active(sd), "no in-flight manifest after the kill"

# cold offline rebuild from a copy of the same durable bytes
from sheep_tpu.serve.reseq import resume_reseq
from sheep_tpu.serve.state import ServeCore
cold = work + "/cold"
shutil.copytree(sd, cold)
os.unlink(cold + "/serve.addr")
ref = ServeCore.open(cold)
out = resume_reseq(ref)
assert out and ref.seq_gen == 1, (out, ref.seq_gen)
want_crc = ref.state_crc()
ref.close()

# the restarted daemon resumes on its own and converges to the SAME crc
os.unlink(sd + "/serve.addr")  # kill -9 left the stale address behind
p = spawn()
c = connect_retry(*addr(), timeout_s=60)
deadline = time.monotonic() + 90
while True:
    st = c.kv("STATS")
    if st.get("seq_gen") == 1:
        break
    assert time.monotonic() < deadline, f"resume never finished: {st}"
    time.sleep(0.2)
assert st["reseqs"] >= 1, st
c.close()
p.send_signal(signal.SIGTERM)
p.wait(timeout=60)
got = ServeCore.open(sd)
assert got.seq_gen == 1 and got.state_crc() == want_crc, \
    (got.seq_gen, got.state_crc(), want_crc)
got.close()
print("reseq smoke ok: detector fired, kill -9 at fold, resumed swap "
      "crc-equal to the cold rebuild (crc=%08x)" % want_crc)
EOF
then
  echo "RESEQ SMOKE FAILED: kill -9 mid-rebuild did not resume to a" \
       "crc-equal re-sequenced generation" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- group-commit smoke (amortized write path, ISSUE 19) -----------------
# A real bin/serve daemon under 4 concurrent writer threads with an armed
# kill -9 at the gc-unsynced boundary — after the deferred WAL append +
# in-memory apply, BEFORE the shared group fsync, the worst spot: the
# in-flight group is torn on disk and never acknowledged.  The restarted
# daemon must recover EVERY acknowledged insert (acked = covered by a
# group fsync, so applied >= acked exactly), reach applied == durable,
# and a post-restart concurrent burst must show the amortization itself
# (one shared fsync sealing multi-record groups).  Seconds of work; a
# regression in the group-commit durability contract fails the gate
# before pytest even runs.
if ! python - <<'EOF'
import os, signal, subprocess, sys, tempfile, threading, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeClient, connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=43)
write_dat(work + "/g.dat", tail, head)
mv = int(max(tail.max(), head.max()))
sd = work + "/state"
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["SHEEP_RESEQ"] = "0"                    # keep the smoke single-path
env["SHEEP_SERVE_DRIFT_MIN"] = "1000000000"

def addr(timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(sd + "/serve.addr").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit("serve.addr never appeared")

def spawn(*args, fault=None):
    e = dict(env)
    if fault:
        e["SHEEP_SERVE_FAULT_PLAN"] = fault
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.cli.serve", "-d", sd, *args],
        env=e, cwd=REPO)

p = spawn("-g", work + "/g.dat", "-k", "3", fault="kill@gc-unsynced:25")
connect_retry(*addr(), timeout_s=60).close()
lock = threading.Lock()
acked = [0]

def writer(w):
    try:
        with ServeClient(*addr(), timeout_s=60) as wc:
            for i in range(400):
                u = (7 * i + w * 911) % (mv + 1)
                v = (13 * i + w * 577 + 1) % (mv + 1)
                wc.insert([(u, v)])
                with lock:  # only counted once the group fsync acked it
                    acked[0] += 1
    except Exception:
        pass  # the daemon died mid-request: exactly the point

threads = [threading.Thread(target=writer, args=(w,), daemon=True)
           for w in range(4)]
for t in threads:
    t.start()
p.wait(timeout=90)
assert p.returncode == 137, f"want kill -9 exit, got {p.returncode}"
for t in threads:
    t.join(timeout=30)

os.unlink(sd + "/serve.addr")  # kill -9 left the stale address behind
p = spawn()
c = connect_retry(*addr(), timeout_s=60)
st = c.kv("STATS")
assert st["applied_seqno"] >= acked[0], ("acked insert lost across the "
                                         "mid-group kill -9", acked[0], st)
assert st["applied_seqno"] == st["durable_seqno"], st

def burst(w):
    with ServeClient(*addr(), timeout_s=60) as wc:
        for i in range(40):
            wc.insert([((3 * i + w) % (mv + 1), (5 * i + w + 1) % (mv + 1))])

threads = [threading.Thread(target=burst, args=(w,), daemon=True)
           for w in range(4)]
for t in threads:
    t.start()
for t in threads:
    t.join(timeout=60)
st = c.kv("STATS")
assert st["applied_seqno"] == st["durable_seqno"] >= acked[0] + 160, st
assert 1 <= st["gc_fsyncs"] <= st["gc_records"], st
assert len(c.part(list(range(50)))) == 50  # the seqlock read path answers
c.request("QUIT")
c.close()
p.send_signal(signal.SIGTERM)
p.wait(timeout=60)
print("group-commit smoke ok: kill -9 at gc-unsynced lost nothing acked "
      "(%d acked, %d recovered)" % (acked[0], st["applied_seqno"]))
EOF
then
  echo "GROUP-COMMIT SMOKE FAILED: kill -9 mid-group lost an acknowledged" \
       "insert or the shared fsync never amortized" >&2
  exit 1
fi
# -------------------------------------------------------------------------

# --- scrub smoke (anti-entropy + self-healing replicas, ISSUE 20) --------
# A real routed leader+follower pair: bit-flip the follower's sealed
# snapshot ON DISK (silent storage rot, not a crash), then drive the
# scrubber — the rotten artifact must be quarantined (renamed, never
# loaded) and repaired back to fsck-clean, the follower's state_crc
# must equal the leader's, and routed reads must answer identically
# before, during and after the episode (the rot never surfaces as
# data).  Seconds of work; a regression in the quarantine/repair
# contract fails the gate before pytest even runs.
if ! python - <<'EOF'
import glob, os, signal, subprocess, sys, tempfile, time
REPO = os.getcwd()
sys.path.insert(0, REPO)
from sheep_tpu.io.edges import write_dat
from sheep_tpu.serve.protocol import ServeClient, ServeError, connect_retry
from sheep_tpu.utils.synth import rmat_edges

work = tempfile.mkdtemp()
tail, head = rmat_edges(7, 4 << 7, seed=47)
write_dat(work + "/g.dat", tail, head)
lead_d, fol_d, route_d = work + "/lead", work + "/fol", work + "/route"
env = dict(os.environ)
env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
env["SHEEP_SERVE_REPL_HB_S"] = "0.1"
env["SHEEP_SERVE_FAILOVER_S"] = "30"
env["SHEEP_RESEQ"] = "0"
env["SHEEP_SERVE_DRIFT"] = "9.0"   # frozen placement: one probe answer

def addr(d, name="serve.addr", timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            host, port = open(f"{d}/{name}").read().split()
            return host, int(port)
        except (OSError, ValueError):
            time.sleep(0.05)
    raise SystemExit(f"{d}/{name} never appeared")

def spawn(mod, d, *args):
    return subprocess.Popen([sys.executable, "-m", mod, "-d", d, *args],
                            env=env, cwd=REPO)

lead = spawn("sheep_tpu.cli.serve", lead_d, "-g", work + "/g.dat",
             "-k", "3", "--role", "leader", "--node-id", "lead",
             "--peers", fol_d)
addr(lead_d)
fol = spawn("sheep_tpu.cli.serve", fol_d, "--role", "follower",
            "--node-id", "fol", "--peers", lead_d)
fh, fp = addr(fol_d)
router = spawn("sheep_tpu.cli.route", route_d,
               "--cluster", f"{lead_d},{fol_d}")
rh, rp = addr(route_d, name="router.addr")
rc = connect_retry(rh, rp, timeout_s=60)
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    try:
        if rc.kv("STATS").get("followers") == 1:
            break
    except ServeError:
        pass
    time.sleep(0.1)
for i in range(6):
    rc.insert([(int(tail[i]), int(head[(i + 3) % len(head)]))])
probe = list(range(64))
expected = rc.part(probe)

# the silent fault: one byte of the follower's sealed snapshot rots
snaps = sorted(glob.glob(fol_d + "/*.snap"))
assert snaps, f"no sealed snapshot in {fol_d}"
with open(snaps[-1], "r+b") as f:
    f.seek(os.path.getsize(snaps[-1]) // 2)
    b = f.read(1)
    f.seek(-1, 1)
    f.write(bytes([b[0] ^ 0x01]))

fc = connect_retry(fh, fp, timeout_s=60)
counts = fc.kv("SCRUB")           # the scrubber: quarantine + repair
assert counts["quarantined"] >= 1, counts
assert counts["repaired"] >= 1, counts
assert counts["unrepaired"] == 0, counts
# routed reads answered identically through the episode
for _ in range(8):
    assert rc.part(probe) == expected, "routed read diverged"
# the quarantined evidence exists and the repaired name fscks clean
quar = glob.glob(fol_d + "/*.quarantined")
assert quar, "no quarantined evidence left behind"
fsck = subprocess.run(
    [sys.executable, "-m", "sheep_tpu.cli.fsck", "-q", fol_d],
    env=env, cwd=REPO, capture_output=True)
assert fsck.returncode == 0, fsck.stdout[-800:] + fsck.stderr[-400:]
# ... and the healed follower is byte-for-byte the leader's state
lh, lp = addr(lead_d)
lc = connect_retry(lh, lp, timeout_s=60)
deadline = time.monotonic() + 60
while time.monotonic() < deadline:
    if fc.kv("STATS")["applied_seqno"] == lc.kv("STATS")["applied_seqno"]:
        break
    time.sleep(0.05)
assert fc.kv("CRC")["crc"] == lc.kv("CRC")["crc"], "state_crc differs"
for cl in (rc, fc, lc):
    try:
        cl.request("QUIT")
        cl.close()
    except (ServeError, OSError):
        pass
for p in (router, lead, fol):
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=60)
print("scrub smoke ok: snapshot rot quarantined + repaired, crc equal, "
      "%d routed reads clean" % (8,))
EOF
then
  echo "SCRUB SMOKE FAILED: snapshot rot escaped the scrubber, the" \
       "repair left the follower divergent, or a routed read saw it" >&2
  exit 1
fi
# -------------------------------------------------------------------------

set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc
