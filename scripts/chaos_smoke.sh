#!/bin/bash
# chaos_smoke.sh — end-to-end smoke of the fault-tolerance subsystem
# (lightgbm_tpu/resilience/), the fast cousin of the slow-marked
# tests/test_chaos.py suite:
#
#   1. kill-resume round trip: train, SIGKILL the process at a seeded
#      mid-run iteration via the fault-injection harness, restart with
#      resume=auto — the final model must be BYTE-identical to the
#      uninterrupted run's;
#   2. corrupt-snapshot skip: truncate the newest snapshot, resume must
#      reject it by name, fall back to the previous one, and still
#      finish byte-identical;
#   3. serving overload: with a tiny in-flight budget and concurrent
#      clients, shed requests get a fast 503 + Retry-After while every
#      accepted response carries exactly the task=predict bytes;
#   4. degraded mode: injected device-dispatch failures flip /healthz
#      to "degraded" with the JAX-free native fallback still serving
#      byte-correct answers.
#
# Exits nonzero on any mismatch.  Stdlib-only clients (no curl).
#
# Usage: scripts/chaos_smoke.sh        (from the repo root or anywhere)

set -u
here="$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)"
PY="${PYTHON:-python3}"
export PYTHONPATH="$here${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# CPU smoke runs stay off the persistent compilation cache (see
# tests/conftest.py: hermetic, and jaxlib 0.9.0 logs an XLA:CPU AOT
# machine-feature error on every cached load); they don't need
# cold-compile amortization.
export LGBM_TPU_NO_COMPILE_CACHE="${LGBM_TPU_NO_COMPILE_CACHE:-1}"

work="$(mktemp -d)"
server_pid=""
cleanup() {
    [ -n "$server_pid" ] && kill -9 "$server_pid" 2>/dev/null
    rm -rf "$work"
}
trap cleanup EXIT

die() { echo "chaos_smoke: FAIL: $*" >&2; exit 1; }

# -- fixture -----------------------------------------------------------
"$PY" - "$work" <<'EOF' || die "fixture generation"
import sys, numpy as np
work = sys.argv[1]
rng = np.random.RandomState(7)
x = rng.randn(400, 6)
y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(int)
with open(work + "/train.tsv", "w") as f:
    for i in range(400):
        f.write("%d\t" % y[i] + "\t".join("%.6g" % v for v in x[i]) + "\n")
EOF

train_args="task=train data=$work/train.tsv objective=binary \
num_iterations=15 num_leaves=7 max_bin=63 min_data_in_leaf=20 metric= verbose=1"

# -- 1. kill-resume round trip -----------------------------------------
"$PY" -m lightgbm_tpu $train_args "output_model=$work/base.txt" \
    > "$work/base.log" 2>&1 || { cat "$work/base.log" >&2; die "base run"; }

chaos_args="$train_args output_model=$work/chaos.txt \
snapshot_period=3 snapshot_dir=$work/snaps resume=auto"
LGBM_TPU_FAULTS="flush.device_get@8=kill" \
    "$PY" -m lightgbm_tpu $chaos_args > "$work/kill.log" 2>&1
rc=$?
[ "$rc" -eq 137 ] || { cat "$work/kill.log" >&2; die "expected SIGKILL (137), got rc=$rc"; }
[ -e "$work/chaos.txt" ] && die "killed run committed a model file"

"$PY" -m lightgbm_tpu $chaos_args > "$work/resume.log" 2>&1 \
    || { cat "$work/resume.log" >&2; die "resume run"; }
grep -q "Resumed from snapshot" "$work/resume.log" \
    || die "resume run did not resume from a snapshot"
cmp -s "$work/base.txt" "$work/chaos.txt" \
    || die "kill-resume model differs from the uninterrupted run"
echo "chaos_smoke: kill-resume round trip byte-identical"

# -- 2. corrupt-snapshot skip ------------------------------------------
rm -f "$work/chaos.txt"
newest="$(ls "$work/snaps" | sort | tail -1)"
"$PY" - "$work/snaps/$newest" <<'EOF'
import sys
p = sys.argv[1]
raw = open(p, "rb").read()
open(p, "wb").write(raw[:len(raw)//2])   # truncate: mid-write crash shape
EOF
"$PY" -m lightgbm_tpu $chaos_args > "$work/resume2.log" 2>&1 \
    || { cat "$work/resume2.log" >&2; die "resume past corrupt snapshot"; }
grep -q "Skipping snapshot .*$newest" "$work/resume2.log" \
    || die "corrupt snapshot $newest not rejected by name"
cmp -s "$work/base.txt" "$work/chaos.txt" \
    || die "corrupt-skip resume model differs from the uninterrupted run"
echo "chaos_smoke: corrupt snapshot skipped, resume byte-identical"

# -- serving fixture: expected predict bytes ---------------------------
"$PY" -m lightgbm_tpu task=predict "data=$work/train.tsv" \
    "input_model=$work/base.txt" "output_result=$work/want.txt" verbose=0 \
    || die "task=predict"

start_server() {   # $1 extra params   $2 env fault spec
    port="$("$PY" -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')"
    LGBM_TPU_FAULTS="$2" "$PY" -m lightgbm_tpu task=serve \
        "input_model=$work/base.txt" "serve_port=$port" \
        serve_batch_timeout_ms=5 $1 > "$work/server.log" 2>&1 &
    server_pid=$!
    "$PY" - "$port" <<'EOF' || { cat "$work/server.log" >&2; die "server did not come up"; }
import sys, time, urllib.request
deadline = time.time() + 120
while time.time() < deadline:
    try:
        urllib.request.urlopen("http://127.0.0.1:%s/healthz" % sys.argv[1],
                               timeout=2).read()
        sys.exit(0)
    except OSError:
        time.sleep(0.2)
sys.exit(1)
EOF
}

stop_server() {
    kill -9 "$server_pid" 2>/dev/null
    wait "$server_pid" 2>/dev/null
    server_pid=""
}

# -- 3. overload: fast 503 + Retry-After, accepted bytes exact ---------
start_server "serve_max_inflight_rows=500" ""
"$PY" - "$port" "$work" <<'EOF' || { cat "$work/server.log" >&2; die "overload probe"; }
import json, sys, threading, urllib.error, urllib.request
port, work = sys.argv[1], sys.argv[2]
base = "http://127.0.0.1:%s" % port
body = open(work + "/train.tsv", "rb").read()
want = open(work + "/want.txt", "rb").read()
results = []
lock = threading.Lock()

def client():
    req = urllib.request.Request(base + "/predict", data=body)
    try:
        with urllib.request.urlopen(req, timeout=60) as r:
            out = (r.status, r.read(), dict(r.headers))
    except urllib.error.HTTPError as e:
        out = (e.code, e.read(), dict(e.headers))
    with lock:
        results.append(out)

threads = [threading.Thread(target=client) for _ in range(8)]
for t in threads: t.start()
for t in threads: t.join(120)

def fail(msg):
    sys.stderr.write("chaos_smoke: FAIL: %s\n" % msg)
    sys.exit(1)

if len(results) != 8:
    fail("a client hung under overload")
ok = shed = 0
for st, got, hdrs in results:
    if st == 200:
        ok += 1
        if got != want:
            fail("accepted request under overload returned bad bytes")
    elif st == 503:
        shed += 1
        if "Retry-After" not in hdrs:
            fail("503 without Retry-After")
        doc = json.loads(got)
        if not doc.get("error"):
            fail("503 body not structured: %r" % doc)
    else:
        fail("unexpected status %d" % st)
if not ok:
    fail("overload shed every request (budget admits an idle server)")
if not shed:
    fail("overload shed nothing (8 x 400 rows vs budget 500)")
print("chaos_smoke: overload shed %d/8, served %d/8 byte-exact" % (shed, ok))
EOF
rc=$?
stop_server
[ "$rc" -eq 0 ] || exit 1

# -- 4. degraded mode: breaker flips to the native fallback ------------
# serve_max_batch_rows=64 pins the warm-up to 3 row buckets = 3
# serve.dispatch hits, so the @4+ schedule spares startup and fails
# every post-warm device dispatch
start_server "serve_breaker_threshold=2 serve_backend=jax serve_max_batch_rows=64" \
    "serve.dispatch@4+=raise:injected device failure"
"$PY" - "$port" "$work" <<'EOF' || { cat "$work/server.log" >&2; die "degraded probe"; }
import json, sys, urllib.request
port, work = sys.argv[1], sys.argv[2]
base = "http://127.0.0.1:%s" % port
body = open(work + "/train.tsv", "rb").read()
want = open(work + "/want.txt", "rb").read()

def fail(msg):
    sys.stderr.write("chaos_smoke: FAIL: %s\n" % msg)
    sys.exit(1)

def post(path, data):
    req = urllib.request.Request(base + path, data=data)
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, r.read()

# warm-up crossed serve.dispatch 3x (3 row buckets); hits 4+ fail, so
# both requests below fail on-device and are answered on the host path
for i in range(2):
    st, got = post("/predict", body)
    if st != 200 or got != want:
        fail("request %d during device failure: status %d or bad bytes" % (i, st))
health = json.loads(urllib.request.urlopen(base + "/healthz", timeout=60).read())
if health.get("status") != "degraded":
    fail("healthz not degraded after repeated dispatch failures: %r" % health)
metrics = urllib.request.urlopen(base + "/metrics", timeout=60).read().decode()
if "lgbm_serve_degraded 1" not in metrics:
    fail("lgbm_serve_degraded gauge not set")
st, got = post("/predict", body)
if st != 200 or got != want:
    fail("degraded-mode serving returned bad bytes")
print("chaos_smoke: degraded mode serves byte-exact on the native fallback")
EOF
rc=$?
stop_server
[ "$rc" -eq 0 ] || exit 1

echo "chaos_smoke: PASS"
