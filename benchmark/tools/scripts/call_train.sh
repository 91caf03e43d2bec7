mkdir -p chiprun_out/benchmark/sft
W=mistral7b-zero3-sft
python3 benchmark/run.py --workload $W --seed 100 --seconds 45 --trace 0 > chiprun_out/benchmark/sft/cold.log 2>&1; rc=$?; echo "cold rc=$rc"; grep "bench +" chiprun_out/benchmark/sft/cold.log | cut -c1-700 | tail -12; tail -1 chiprun_out/benchmark/sft/cold.log | cut -c1-800
[ $rc -eq 0 ] || { tail -30 chiprun_out/benchmark/sft/cold.log | cut -c1-600; exit 1; }
for s in 1 2 3; do python3 benchmark/run.py --workload $W --seed $s --seconds 45 --trace 0 > chiprun_out/benchmark/sft/a$s.log 2>&1; echo "a$s rc=$?"; tail -1 chiprun_out/benchmark/sft/a$s.log | cut -c1-600; done
python3 benchmark/run.py --workload $W --seed 9 --seconds 45 --trace 1 > chiprun_out/benchmark/sft/trace.log 2>&1; echo "trace rc=$?"; grep "bench +" chiprun_out/benchmark/sft/trace.log | cut -c1-900 | tail -6; tail -1 chiprun_out/benchmark/sft/trace.log | cut -c1-3000
for s in 4 5 6; do python3 benchmark/run.py --workload $W --seed $s --seconds 45 --trace 0 > chiprun_out/benchmark/sft/b$s.log 2>&1; echo "b$s rc=$?"; tail -1 chiprun_out/benchmark/sft/b$s.log | cut -c1-600; done
grep "bench +" chiprun_out/benchmark/sft/b6.log | cut -c1-700 | tail -12
rm -rf chiprun_out/benchmark/mistral7b-zero3-sft/trace/plugins
