"""``rpc.spawn`` keeps strong references to fire-and-forget tasks in ONE
set for every event loop of the process. With more than 512 tasks in
flight (512 streams on one replica: PR 39's cell) every spawn prunes
the set, and a prune that walks the set itself while another loop's
thread adds or ends a task raises "Set changed size during iteration"
inside the caller's receive loop: the connection is lost and the actor
behind it reads as dead."""
import asyncio
import sys
import threading
import time

from ray_tpu._private import rpc


def test_spawn_prunes_while_another_loop_spawns():
    errors = []
    stop = threading.Event()

    def run(hold: int):
        async def main():
            async def nap(s):
                await asyncio.sleep(s)

            held = [rpc.spawn(nap(30)) for _ in range(hold)]
            try:
                while not stop.is_set():
                    for _ in range(50):
                        rpc.spawn(nap(0))
                    await asyncio.sleep(0)
            except Exception as e:  # noqa: BLE001 - the fault under test
                errors.append(e)
            finally:
                for t in held:
                    t.cancel()
                await asyncio.gather(*held, return_exceptions=True)

        asyncio.run(main())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    # 600 held on one loop: every spawn anywhere prunes; three loops
    threads = [threading.Thread(target=run, args=(n,))
               for n in (600, 0, 0)]
    try:
        for t in threads:
            t.start()
        time.sleep(1.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
