"""``rla-tpu`` CLI: per-host agents + multi-machine driver launches.

The reference's multi-node entry is ``ray up cluster.yaml`` +
``ray submit cluster.yaml train.py`` (reference: README.md:57-62): Ray's
cluster launcher starts a daemon on every node, then the driver script
connects with ``ray.init(address=...)``.  The no-Ray equivalent:

1. on every host: ``rla-tpu agent --port 7777``
2. on the driver: ``rla-tpu launch --agents host1:7777,host2:7777 train.py``
   (or run the script directly with ``RLA_TPU_AGENTS`` set, or pass
   ``--address host1:7777,host2:7777`` to the examples)

``launch`` exports the agent list as ``RLA_TPU_AGENTS`` and runs the
script; anything calling ``runtime.bootstrap.launch_distributed`` (or an
accelerator with ``num_hosts > 1``) picks the agents up from the
environment via ``runtime.agent.agents_from_env``.
"""

from __future__ import annotations

import argparse
from typing import List, Optional


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(
        "rla-tpu", description="TPU training control plane")
    sub = parser.add_subparsers(dest="cmd", required=True)

    ag = sub.add_parser("agent", help="run a per-host worker agent")
    ag.add_argument("--port", type=int, default=7777)
    ag.add_argument("--bind", default="127.0.0.1",
                    help="interface to listen on (agents execute arbitrary "
                         "pickled code; non-loopback binds should set "
                         "RLA_TPU_AGENT_TOKEN on agent and driver)")

    la = sub.add_parser(
        "launch", help="run a driver script against host agents")
    la.add_argument("--agents", required=True,
                    help="comma-separated host:port agent addresses")
    la.add_argument("script", help="driver python script")
    la.add_argument("script_args", nargs=argparse.REMAINDER)

    tr = sub.add_parser(
        "trace", help="summarize an XPlane device trace directory "
                      "(written by Profiler.start_trace) as a per-op / "
                      "per-category roofline table, each op with its "
                      "named scope where scopes.json lies beside it")
    tr.add_argument("trace_dir", help="directory passed to start_trace")
    tr.add_argument("--top", type=int, default=25,
                    help="rows in the per-op table (0 = all)")

    args = parser.parse_args(argv)
    if args.cmd == "agent":
        from .runtime.agent import HostAgent
        # a tokenless non-loopback bind raises inside HostAgent (RCE
        # surface; RLA_TPU_ALLOW_TOKENLESS_BIND=1 is the explicit opt-out)
        HostAgent(args.port, args.bind).serve_forever()
    elif args.cmd == "launch":
        import os
        import runpy
        import sys

        os.environ["RLA_TPU_AGENTS"] = args.agents
        sys.argv = [args.script] + list(args.script_args)
        runpy.run_path(args.script, run_name="__main__")
    elif args.cmd == "trace":
        from .utils.profiler import trace_op_summary

        s = trace_op_summary(args.trace_dir, top=args.top)
        print(f"device total: {s['total_ms']:.2f} ms\n")
        print(f"{'category':<26} {'self ms':>10} {'GB/s':>8} "
              f"{'TF/s':>7} {'%':>6}")
        for cat, row in sorted(s["by_category"].items(),
                               key=lambda kv: -kv[1]["self_ms"]):
            print(f"{cat:<26} {row['self_ms']:>10.2f} {row['gbps']:>8.1f} "
                  f"{row['tfs']:>7.1f} {row['pct']:>6.1f}")
        # scopes.json lay beside the trace (Profiler.stop_trace): each
        # op's named-scope stack in the program that ran most of it
        scope = (f"  scope in {s['scope_program']}"
                 if "scope_program" in s else "")
        print(f"\n{'op':<44} {'self ms':>10} {'n':>6} {'%':>6}{scope}")
        for op in s["ops"]:
            print(f"{op['name'][:44]:<44} {op['self_ms']:>10.2f} "
                  f"{op['count']:>6d} {op['pct']:>6.1f}"
                  f"  {op.get('scope', '')}".rstrip())


if __name__ == "__main__":
    main()
