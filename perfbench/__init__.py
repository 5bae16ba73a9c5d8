"""perfbench: tpunet's benchmark. Everything the yardstick needs lives here;
from the program it imports `tpunet` and nothing else of the repository."""
