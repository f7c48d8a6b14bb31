#!/bin/bash
# serve_smoke.sh — end-to-end smoke of the task=serve subsystem:
# start the server, round-trip one predict (bytes must equal
# task=predict's), scrape /metrics, hot-swap via /reload (bytes must
# equal task=predict under the NEW model), then SIGTERM-drain.
# Then the multi-process leg (serving/frontend.py): start 4
# SO_REUSEPORT workers, byte-compare responses vs task=predict,
# SIGKILL one worker UNDER LOAD and assert the fleet keeps answering
# + the supervisor respawns the slot, scrape per-worker liveness from
# /metrics, then SIGTERM-drain the whole front-end.
# Exits nonzero on any mismatch.  Stdlib-only clients (no curl).
#
# Usage: scripts/serve_smoke.sh        (from the repo root or anywhere)

set -u
here="$(CDPATH= cd -- "$(dirname -- "$0")/.." && pwd)"
PY="${PYTHON:-python3}"
export PYTHONPATH="$here${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# CPU smoke runs stay off the persistent compilation cache (see
# tests/conftest.py); they don't need cold-compile amortization.
export LGBM_TPU_NO_COMPILE_CACHE="${LGBM_TPU_NO_COMPILE_CACHE:-1}"

work="$(mktemp -d)"
server_pid=""
fe_pid=""
cleanup() {
    [ -n "$server_pid" ] && kill -9 "$server_pid" 2>/dev/null
    if [ -n "$fe_pid" ]; then
        # the front-end supervisor fans SIGTERM out to its workers;
        # give it a moment, then hard-kill the process group
        kill -TERM "$fe_pid" 2>/dev/null
        sleep 1
        kill -9 "$fe_pid" 2>/dev/null
    fi
    rm -rf "$work"
}
trap cleanup EXIT

die() { echo "serve_smoke: FAIL: $*" >&2; exit 1; }

# -- fixture: two tiny models + a request body -------------------------
"$PY" - "$work" <<'EOF' || die "fixture generation"
import sys, numpy as np
work = sys.argv[1]
model = """gbdt
num_class=1
label_index=0
max_feature_idx=3
sigmoid=1
objective=binary

Tree=0
num_leaves=3
split_feature=0 2
split_gain=1 0.5
threshold=0.5 -0.25
left_child=1 -2
right_child=-1 -3
leaf_parent=0 1 1
leaf_value=0.2 -0.13 0.34
internal_value=0 0.1

feature importance:
"""
open(work + "/model_a.txt", "w").write(model)
open(work + "/model_b.txt", "w").write(
    model.replace("leaf_value=0.2 -0.13 0.34",
                  "leaf_value=0.7 -0.6 0.5"))
rng = np.random.RandomState(0)
with open(work + "/data.tsv", "w") as f:
    for row in rng.randn(25, 4):
        f.write("0\t" + "\t".join("%.6g" % v for v in row) + "\n")
EOF

# -- expected bytes via the batch path ---------------------------------
for m in a b; do
    "$PY" -m lightgbm_tpu task=predict "data=$work/data.tsv" \
        "input_model=$work/model_$m.txt" \
        "output_result=$work/want_$m.txt" verbose=0 \
        || die "task=predict ($m)"
done

# -- start the server --------------------------------------------------
port="$("$PY" -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')"
"$PY" -m lightgbm_tpu task=serve "input_model=$work/model_a.txt" \
    "serve_port=$port" serve_batch_timeout_ms=1 \
    > "$work/server.log" 2>&1 &
server_pid=$!

"$PY" - "$port" <<'EOF' || { cat "$work/server.log" >&2; die "server did not come up"; }
import sys, time, urllib.request
port = sys.argv[1]
deadline = time.time() + 120
while time.time() < deadline:
    try:
        urllib.request.urlopen("http://127.0.0.1:%s/healthz" % port,
                               timeout=2).read()
        sys.exit(0)
    except OSError:
        time.sleep(0.2)
sys.exit(1)
EOF

# -- predict round trip + /metrics + /reload ---------------------------
"$PY" - "$port" "$work" <<'EOF' || { cat "$work/server.log" >&2; exit 1; }
import json, sys, urllib.request
port, work = sys.argv[1], sys.argv[2]
base = "http://127.0.0.1:%s" % port

def post(path, data, ctype="text/plain"):
    req = urllib.request.Request(base + path, data=data,
                                 headers={"Content-Type": ctype})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read()

def fail(msg):
    sys.stderr.write("serve_smoke: FAIL: %s\n" % msg)
    sys.exit(1)

body = open(work + "/data.tsv", "rb").read()
want_a = open(work + "/want_a.txt", "rb").read()
want_b = open(work + "/want_b.txt", "rb").read()

got = post("/predict", body)
if got != want_a:
    fail("served bytes differ from task=predict (model A)")

metrics = urllib.request.urlopen(base + "/metrics", timeout=60).read().decode()
for needle in ("lgbm_serve_rows_total 25",
               'lgbm_serve_requests_total{endpoint="/predict",code="200"} 1',
               "lgbm_serve_batches_total",
               "lgbm_serve_request_latency_seconds_count"):
    if needle not in metrics:
        fail("metrics scrape missing %r" % needle)

# -- single-row low-latency lane: byte-compare vs task=predict ---------
# a 1-row request routes through the synchronous fast lane (the 25-row
# body above exceeded the lane bound and batched); its bytes must be
# the matching line of task=predict's output
import time as _time
one = body.split(b"\n", 1)[0] + b"\n"
want_one = want_a.split(b"\n", 1)[0] + b"\n"
t0 = _time.monotonic()
got_one = post("/predict", one)
lat_ms = (_time.monotonic() - t0) * 1e3
if got_one != want_one:
    fail("fast-lane single-row bytes differ from task=predict")
metrics = urllib.request.urlopen(base + "/metrics", timeout=60).read().decode()
for needle in ('lgbm_serve_lane_requests_total{lane="fast"} 1',
               'lgbm_serve_lane_requests_total{lane="batch"} 1',
               "lgbm_serve_batcher_queue_depth 0",
               'lgbm_serve_lane_latency_seconds_count{lane="fast"} 1'):
    if needle not in metrics:
        fail("lane metrics scrape missing %r" % needle)
print("serve_smoke: fast-lane single row OK (%.2f ms)" % lat_ms)

info = json.loads(post("/reload",
                       json.dumps({"model": work + "/model_b.txt"}).encode(),
                       "application/json"))
if info.get("source") != work + "/model_b.txt":
    fail("reload did not report the new model: %r" % info)

got = post("/predict", body)
if got != want_b:
    fail("post-reload bytes differ from task=predict (model B)")
if got == want_a:
    fail("reload did not change predictions")

health = json.loads(urllib.request.urlopen(base + "/healthz",
                                           timeout=60).read())
if health.get("status") != "ok":
    fail("healthz not ok after reload: %r" % health)

# -- reload FAILURE: structured error, counted, old forest keeps serving
import urllib.error
try:
    post("/reload", json.dumps({"model": work + "/no_such_model.txt"}).encode(),
         "application/json")
    fail("reload of a missing model did not error")
except urllib.error.HTTPError as e:
    if e.code != 400:
        fail("reload failure status %d, want 400" % e.code)
    doc = json.loads(e.read())
    if not doc.get("error") or not doc.get("message"):
        fail("reload failure body not structured: %r" % doc)
metrics = urllib.request.urlopen(base + "/metrics", timeout=60).read().decode()
if "lgbm_serve_reload_failures_total 1" not in metrics:
    fail("lgbm_serve_reload_failures_total not incremented")
got = post("/predict", body)
if got != want_b:
    fail("old forest not serving after failed reload")
print("serve_smoke: predict + metrics + reload + reload-failure OK")
EOF
rc=$?
[ "$rc" -eq 0 ] || die "round trip (rc=$rc)"

# -- graceful drain ----------------------------------------------------
kill -TERM "$server_pid"
for _ in $(seq 1 100); do
    kill -0 "$server_pid" 2>/dev/null || break
    sleep 0.1
done
if kill -0 "$server_pid" 2>/dev/null; then
    die "server did not drain within 10s of SIGTERM"
fi
wait "$server_pid"
rc=$?
server_pid=""
[ "$rc" -eq 0 ] || die "server exited nonzero on SIGTERM drain (rc=$rc)"

# -- multi-process front-end leg (serving/frontend.py) -----------------
fe_port="$("$PY" -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')"
"$PY" -m lightgbm_tpu task=serve "input_model=$work/model_a.txt" \
    "serve_port=$fe_port" serve_workers=4 serve_batch_timeout_ms=1 \
    > "$work/frontend.log" 2>&1 &
fe_pid=$!

"$PY" - "$fe_port" <<'EOF' || { cat "$work/frontend.log" >&2; die "front-end did not come up"; }
import sys, time, urllib.request
port = sys.argv[1]
deadline = time.time() + 180
while time.time() < deadline:
    try:
        urllib.request.urlopen("http://127.0.0.1:%s/healthz" % port,
                               timeout=2).read()
        sys.exit(0)
    except OSError:
        time.sleep(0.2)
sys.exit(1)
EOF

"$PY" - "$fe_port" "$work" <<'EOF' || { tail -40 "$work/frontend.log" >&2; exit 1; }
import json, os, signal, sys, threading, time, urllib.request
port, work = sys.argv[1], sys.argv[2]
base = "http://127.0.0.1:%s" % port

def fail(msg):
    sys.stderr.write("serve_smoke: FAIL(frontend): %s\n" % msg)
    sys.exit(1)

def post_predict(body, timeout=60):
    req = urllib.request.Request(base + "/predict", data=body,
                                 headers={"Content-Type": "text/plain"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()

body = open(work + "/data.tsv", "rb").read()
want = open(work + "/want_a.txt", "rb").read()

# every connection may land on a different worker (SO_REUSEPORT picks
# per connection): bytes must match task=predict on all of them
for _ in range(8):
    if post_predict(body) != want:
        fail("front-end bytes differ from task=predict")

# discover the worker pids through repeated /healthz scrapes (each
# scrape is a fresh connection, so the kernel rotates us around the
# fleet) — all 4 should answer eventually
def scrape_pids(need, deadline_s=60):
    pids, deadline = {}, time.time() + deadline_s
    while len(pids) < need and time.time() < deadline:
        doc = json.loads(urllib.request.urlopen(
            base + "/healthz", timeout=10).read())
        w = doc.get("worker")
        if not w:
            fail("healthz has no worker identity: %r" % doc)
        pids[int(w["pid"])] = int(w["index"])
    return pids

pids = scrape_pids(4)
if len(pids) < 2:
    fail("only saw %d distinct worker pids via /healthz" % len(pids))

# per-worker liveness on /metrics
metrics = urllib.request.urlopen(base + "/metrics", timeout=30).read().decode()
if 'lgbm_serve_worker{index="' not in metrics:
    fail("metrics scrape missing lgbm_serve_worker liveness series")

# SIGKILL one worker UNDER LOAD: the fleet must keep answering
# byte-identically (only the victim's own connections may error) and
# the supervisor must respawn the slot
stop = threading.Event()
errors = []
def hammer():
    while not stop.is_set():
        try:
            if post_predict(body, timeout=30) != want:
                errors.append("bytes diverged under kill load")
                return
        except OSError:
            pass   # the killed worker's own connection: allowed
ts = [threading.Thread(target=hammer) for _ in range(4)]
for t in ts:
    t.start()
victim = sorted(pids)[0]
time.sleep(0.3)
os.kill(victim, signal.SIGKILL)
time.sleep(1.0)
stop.set()
for t in ts:
    t.join()
if errors:
    fail(errors[0])
# fleet still answers, and a NEW pid appears (the respawned slot)
if post_predict(body) != want:
    fail("front-end bytes differ after worker SIGKILL")
deadline = time.time() + 120
respawned = False
while time.time() < deadline:
    seen = scrape_pids(4, deadline_s=10)
    if victim in seen:
        seen.pop(victim)   # stale scrape raced the kill
    if any(p not in pids for p in seen):
        respawned = True
        break
    time.sleep(0.5)
if not respawned:
    fail("no respawned worker pid appeared within 120s of SIGKILL")
print("serve_smoke: front-end predict + kill-respawn + liveness OK")
EOF
rc=$?
[ "$rc" -eq 0 ] || die "front-end leg (rc=$rc)"

# -- front-end graceful drain ------------------------------------------
kill -TERM "$fe_pid"
for _ in $(seq 1 300); do
    kill -0 "$fe_pid" 2>/dev/null || break
    sleep 0.1
done
if kill -0 "$fe_pid" 2>/dev/null; then
    die "front-end did not drain within 30s of SIGTERM"
fi
wait "$fe_pid"
rc=$?
fe_pid=""
[ "$rc" -eq 0 ] || die "front-end exited nonzero on SIGTERM drain (rc=$rc)"

echo "serve_smoke: PASS"
