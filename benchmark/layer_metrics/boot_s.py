"""``boot_s`` (layer: entry).  Training: ``TFCluster.run`` called until
``main_fun`` is entered on every node.  Serving: ``Server.__enter__``
until the replica is ready.  The benchmark's clock around the program's
entry points."""


def read(facts):
    return facts.get("boot_s")
