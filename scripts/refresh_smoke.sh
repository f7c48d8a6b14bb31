#!/bin/bash
# refresh_smoke.sh — end-to-end smoke of continuous train->deploy
# (lightgbm_tpu/refresh/), the fast cousin of the slow-marked
# tests/test_refresh.py leg:
#
#   1. train a champion on a slice, serve it, and capture the
#      task=predict bytes for the held-out rows;
#   2. drop fresh data and run ONE refresh cycle with the CHAOS kill
#      armed at deploy.push@1: the agent ingests the drop
#      (refresh_ingest=true -> task=ingest shard pass), warm-start
#      retrains from the champion (init_model continued training over
#      the shard directory), then dies the instant it would push —
#      the fleet must still answer BYTE-identically to the champion;
#   3. rerun the agent clean: the interrupted cycle replays
#      deterministically (ingest -> retrain -> push -> shadow-eval ->
#      promote), and the served bytes flip to exactly what
#      task=predict writes under the promoted challenger.
#
# Exits nonzero on any mismatch.  Stdlib-only clients (no curl).
#
# Usage: scripts/refresh_smoke.sh      (from the repo root or anywhere)

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
cleanup() {
    [ -n "$server_pid" ] && kill -9 "$server_pid" 2>/dev/null
    rm -rf "$work"
}
trap cleanup EXIT

die() { echo "refresh_smoke: FAIL: $*" >&2; exit 1; }

# -- fixture: base slice, drop batch, held-out eval rows ---------------
"$PY" - "$work" <<'EOF' || die "fixture generation"
import os, sys, numpy as np
work = sys.argv[1]
rng = np.random.RandomState(11)
n = 900
x = rng.randn(n, 6)
y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(int)
def dump(path, a, b):
    with open(path, "w") as f:
        for i in range(a, b):
            f.write("%d\t" % y[i]
                    + "\t".join("%.6g" % v for v in x[i]) + "\n")
dump(work + "/base.tsv", 0, 200)
os.makedirs(work + "/drop")
dump(work + "/drop/batch1.tsv", 200, 700)
dump(work + "/eval.tsv", 700, 900)
EOF

targs="objective=binary num_leaves=7 max_bin=63 min_data_in_leaf=20 metric= verbose=0"

# -- champion + its expected predict bytes -----------------------------
"$PY" -m lightgbm_tpu task=train "data=$work/base.tsv" \
    "output_model=$work/champion.txt" num_iterations=5 $targs \
    || die "champion training"
"$PY" -m lightgbm_tpu task=predict "data=$work/eval.tsv" \
    "input_model=$work/champion.txt" \
    "output_result=$work/want_champ.txt" verbose=0 \
    || die "task=predict (champion)"

# -- serve the champion ------------------------------------------------
port="$("$PY" -c 'import socket; s=socket.socket(); s.bind(("127.0.0.1",0)); print(s.getsockname()[1]); s.close()')"
"$PY" -m lightgbm_tpu task=serve "input_model=$work/champion.txt" \
    "serve_port=$port" serve_batch_timeout_ms=1 serve_backend=native \
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

agent_args="task=refresh refresh_drop_dir=$work/drop \
refresh_serve_url=http://127.0.0.1:$port \
refresh_eval_data=$work/eval.tsv input_model=$work/champion.txt \
refresh_ingest=true refresh_max_cycles=1 refresh_period_s=0 \
refresh_poll_s=0.1 refresh_deadline_s=240 refresh_rounds=10 \
refresh_status_port=-1 $targs verbose=1"

# -- chaos leg: SIGKILL the agent the instant it would push ------------
LGBM_TPU_FAULTS="deploy.push@1=kill" \
    "$PY" -m lightgbm_tpu $agent_args > "$work/agent_kill.log" 2>&1
rc=$?
[ "$rc" -eq 137 ] || [ "$rc" -eq 265 ] \
    || { cat "$work/agent_kill.log" >&2; \
         die "expected the injected SIGKILL (exit $rc)"; }

"$PY" - "$port" "$work" champ <<'EOF' || { cat "$work/server.log" >&2; die "champion byte-compare after the killed refresh"; }
import sys, urllib.request
port, work, tag = sys.argv[1], sys.argv[2], sys.argv[3]
body = open(work + "/eval.tsv", "rb").read()
req = urllib.request.Request("http://127.0.0.1:%s/predict" % port,
                             data=body,
                             headers={"Content-Type": "text/plain"})
got = urllib.request.urlopen(req, timeout=120).read()
want = open(work + "/want_%s.txt" % tag, "rb").read()
assert got == want, "served bytes diverged from task=predict (%s)" % tag
EOF

# -- rerun converges: ingest -> retrain -> eval -> promote -------------
"$PY" -m lightgbm_tpu $agent_args > "$work/agent_ok.log" 2>&1 \
    || { cat "$work/agent_ok.log" >&2; die "refresh rerun"; }
grep -q "refresh cycle 0: promoted" "$work/agent_ok.log" \
    || { cat "$work/agent_ok.log" >&2; die "rerun did not promote"; }

chall="$work/drop/.refresh/challenger_0000.txt"
[ -f "$chall" ] || die "challenger model missing"
"$PY" -m lightgbm_tpu task=predict "data=$work/eval.tsv" \
    "input_model=$chall" "output_result=$work/want_chall.txt" \
    verbose=0 || die "task=predict (challenger)"

"$PY" - "$port" "$work" chall <<'EOF' || { cat "$work/server.log" >&2; die "challenger byte-compare after promotion"; }
import sys, urllib.request
port, work, tag = sys.argv[1], sys.argv[2], sys.argv[3]
body = open(work + "/eval.tsv", "rb").read()
req = urllib.request.Request("http://127.0.0.1:%s/predict" % port,
                             data=body,
                             headers={"Content-Type": "text/plain"})
got = urllib.request.urlopen(req, timeout=120).read()
want = open(work + "/want_%s.txt" % tag, "rb").read()
assert got == want, "served bytes diverged from task=predict (%s)" % tag
EOF

kill -TERM "$server_pid" 2>/dev/null
wait "$server_pid" 2>/dev/null
server_pid=""

echo "refresh_smoke: PASS (kill at deploy.push left the champion serving byte-identically; rerun promoted the challenger)"
