"""kv_store.wal_off: the store runs without its write-ahead log
(``durability="none"``): writes are acknowledged before anything reaches
a log, the step a later change might take for speed.  The configuration
states append-before-ack, so ``lost_writes`` must read over its limit."""

FAILS = "lost_writes"
CONFIG_OVERRIDES = {"spec": {"durability": "none"}}
