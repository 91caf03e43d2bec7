O=$PWD/chiprun_out/benchmark/final; mkdir -p $O; cd _proof
C=mistral7b-chat-steady; D=mistral7b-doc-batch
run() { python3 benchmark/run.py --workload $2 --seed $3 --seconds 45 --trace $4 > $O/$1.log 2>&1; rc=$?
  echo "== $1 rc=$rc"; grep "window:\|also:\|reference check\|warm-up done\|worker ready\|traced\|NOT CORRECT\|FAILED\|REFUSED" $O/$1.log | cut -c1-900; tail -1 $O/$1.log | cut -c1-2500; return $rc; }
run doc_cold $D 100 0 || tail -30 $O/doc_cold.log | cut -c1-400
run doc_a1 $D 1 0; run chat_a1 $C 1 0; run doc_a2 $D 2 0; run chat_a2 $C 2 0; run doc_a3 $D 3 0
run doc_b5 $D 5 0; run chat_b5 $C 5 0; run doc_b6 $D 6 0; run chat_b6 $C 6 0; run doc_b7 $D 7 0
run doc_trace $D 9 1
