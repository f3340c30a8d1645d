"""Print a page about a profiler trace: planes, lines, the first events of each with their
stats. For whoever writes a reader against a trace from a device they have not seen.

    python benchmark/tools/describe_trace.py <trace dir or .xplane.pb> [events per line]
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import xtrace  # noqa: E402

if __name__ == "__main__":
    target = Path(sys.argv[1])
    path = target if target.is_file() else xtrace.find_xplane(target)
    print(xtrace.describe(path, int(sys.argv[2]) if len(sys.argv) > 2 else 12))
