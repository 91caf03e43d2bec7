mkdir -p chiprun_out/benchmark
python3 benchmark/tools/probe_build.py --config mistral-7b-v0.3-serve-l12 --blocks 700,560,460 > chiprun_out/benchmark/probe.log 2>&1; echo "probe rc=$?"; grep "PROBE\|device" chiprun_out/benchmark/probe.log | cut -c1-700
NB=$(python3 -c "import json; print(json.load(open('chiprun_out/benchmark/probe_build.json'))['num_blocks'])") || exit 1
echo "num_blocks=$NB"
python3 - <<PY
import json
p = "benchmark/configs/mistral-7b-v0.3-serve-l12.json"
c = json.load(open(p)); c["engine"]["num_blocks"] = $NB; json.dump(c, open(p, "w"), indent=2)
PY
python3 benchmark/run.py --workload mistral7b-chat-steady --seed 1 --seconds 5 --trace 1 > chiprun_out/benchmark/call2_trace.log 2>&1; echo "trace rc=$?"; grep "bench +" chiprun_out/benchmark/call2_trace.log | cut -c1-1500 | tail -8; tail -1 chiprun_out/benchmark/call2_trace.log | cut -c1-3500
python3 benchmark/tools/find_knee.py --workload mistral7b-chat-steady --rates 2,4,6,8,10,12,14 --seconds 15 --closed mistral7b-doc-batch --closed-seconds 25 --tag chunk128 > chiprun_out/benchmark/knee128.log 2>&1; echo "knee128 rc=$?"; grep "RATE\|CLOSED\|KNEE\|warm-up\|ready\|REFUSED" chiprun_out/benchmark/knee128.log | cut -c1-700
python3 benchmark/tools/find_knee.py --workload mistral7b-chat-steady --rates 4,8 --seconds 15 --closed mistral7b-doc-batch --closed-seconds 25 --engine '{"chunk": 256}' --tag chunk256 > chiprun_out/benchmark/knee256.log 2>&1; echo "knee256 rc=$?"; grep "RATE\|CLOSED\|KNEE\|warm-up\|ready\|REFUSED" chiprun_out/benchmark/knee256.log | cut -c1-700
python3 benchmark/run.py --workload mistral7b-chat-steady --seed 2 --seconds 20 --trace 0 > chiprun_out/benchmark/call2_chat_e2e.log 2>&1; echo "chat e2e rc=$?"; grep "bench +" chiprun_out/benchmark/call2_chat_e2e.log | cut -c1-900 | tail -8; tail -1 chiprun_out/benchmark/call2_chat_e2e.log | cut -c1-1500
