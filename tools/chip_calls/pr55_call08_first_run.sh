#!/bin/bash
# PR 55, chip call 8 (1 chip): call 7 read the change's warm set-up 3 s over the parent's with the shape ladder 1.5 s
# longer and the programs' build-or-load time level: does the new kernel cost more to start?  Two processes, the second
# reading the first's compile cache.
out=/root/repo/chiprun_out/p55c8; mkdir -p $out
for i in 1 2; do python3 tools/chip_calls/pr55_first_run.py committed parent committed parent 2> $out/first_run.$i.err | tee $out/first_run.$i.jsonl; done
exit 0
