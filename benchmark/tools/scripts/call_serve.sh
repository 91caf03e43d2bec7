O=chiprun_out/benchmark/serve; mkdir -p $O
C=mistral7b-chat-steady; D=mistral7b-doc-batch
run() { # name workload seed trace
  python3 benchmark/run.py --workload $2 --seed $3 --seconds 45 --trace $4 > $O/$1.log 2>&1; rc=$?
  echo "== $1 rc=$rc"; grep "window:\|also:\|reference check\|warm-up done\|worker ready\|traced\|NOT CORRECT\|FAILED\|REFUSED" $O/$1.log | cut -c1-1200; tail -1 $O/$1.log | cut -c1-2500; return $rc; }
run doc_cold $D 100 0 || { tail -40 $O/doc_cold.log | cut -c1-500; exit 1; }
run chat_cold $C 100 0 || { tail -40 $O/chat_cold.log | cut -c1-500; exit 1; }
run doc_trace $D 9 1
run chat_trace $C 9 1
for s in 1 2 3; do run doc_a$s $D $s 0; run chat_a$s $C $s 0; done
for s in 5 6 7; do run doc_b$s $D $s 0; run chat_b$s $C $s 0; done
rm -rf chiprun_out/benchmark/*/trace/plugins
