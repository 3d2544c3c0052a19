"""Round-4 feature tour: async actors, serve streaming over the
worker-hosted proxy, and a durable workflow with a dynamic
continuation. Runs on CPU (no TPU needed):

    JAX_PLATFORMS=cpu python examples/async_serve_workflow.py
"""

import json
import tempfile
import time
import urllib.request

import ray_tpu
from ray_tpu import serve, workflow


def main():
    ray_tpu.init(num_cpus=4, max_process_workers=2)

    # -- async actor: overlapping awaits + streaming method ------------
    @ray_tpu.remote
    class Fetcher:
        async def get(self, k):
            import asyncio
            await asyncio.sleep(0.05)
            return k * 2

        async def stream(self, n):
            import asyncio
            for i in range(n):
                await asyncio.sleep(0.01)
                yield {"i": i}

    f = Fetcher.remote()
    t0 = time.perf_counter()
    vals = ray_tpu.get([f.get.remote(i) for i in range(20)])
    print(f"async actor: 20 overlapped calls in "
          f"{time.perf_counter() - t0:.2f}s -> {vals[:5]}...")
    items = [ray_tpu.get(r) for r in
             f.stream.options(num_returns="streaming").remote(3)]
    print("async generator streamed:", items)

    # -- serve: streaming response through the worker-hosted proxy -----
    @serve.deployment(num_replicas=2)
    class Tokens:
        async def __call__(self, body=None):
            import asyncio
            for tok in ("the", "quick", "brown", "fox"):
                await asyncio.sleep(0.02)
                yield tok

    serve.start(http=True, proxy_location="worker")
    serve.run(Tokens.bind())
    host, port = serve.http_address()
    req = urllib.request.Request(
        f"http://{host}:{port}/Tokens?stream=1", data=b"",
        method="POST")
    deadline = time.time() + 30
    while True:
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                toks = [json.loads(line) for line in resp
                        if line.strip()]
            break
        except urllib.error.HTTPError:
            if time.time() > deadline:
                raise
            time.sleep(0.2)
    print("serve streamed over HTTP chunked:", toks)
    serve.shutdown()

    # -- workflow: durable steps + a dynamic continuation --------------
    @ray_tpu.remote
    def add(a, b):
        return a + b

    @ray_tpu.remote
    def fib(n):
        from ray_tpu import workflow as wf
        if n <= 1:
            return n
        return wf.continuation(add.bind(fib.bind(n - 1),
                                        fib.bind(n - 2)))

    store = tempfile.mkdtemp()
    out = workflow.run(fib.bind(9), workflow_id="fib9", storage=store)
    print("workflow fib(9) via dynamic continuations:", out)
    print("resume from storage:", workflow.resume("fib9", store))

    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
