"""Tell a stall of the whole machine from one of the benchmark's process.

    python3 benchmarks/host_stall_monitor.py chiprun_out/monitor.log &

No JAX, no chip: a loop that sleeps 20 ms and writes down, with the wall
clock, every time it woke more than 0.1 s late.  Start it in the
background at the head of a chip call's script, kill it at the end, and
lay its lines over the runs' start and end times: a run of a training cell
that lost steps while this loop was late lost them to the machine
(PERF.md section 7, "Throughput dips in single runs"), not to the program.
"""
import sys
import time

TICK_S, LATE_S = 0.02, 0.1


def main(path: str) -> None:
    with open(path, "a", buffering=1) as out:
        last = time.perf_counter()
        while True:
            time.sleep(TICK_S)
            now = time.perf_counter()
            if now - last - TICK_S > LATE_S:
                out.write(f"{time.time():.3f} late_by "
                          f"{now - last - TICK_S:.3f}\n")
            last = now


if __name__ == "__main__":
    main(sys.argv[1])
