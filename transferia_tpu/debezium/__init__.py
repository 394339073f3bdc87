"""Debezium protocol codec (reference: pkg/debezium/ — emitter_*.go,
receiver.go, per-DB type mappers).

Bidirectional: the emitter turns ChangeItems/ColumnBatches into Debezium
envelope (key, value) JSON pairs for queue sinks (mysql2kafka config in
BASELINE.json); the receiver turns Debezium envelopes back into ChangeItems
for queue sources.  Type fidelity follows Kafka Connect schema names
(io.debezium.time.*, org.apache.kafka.connect.data.Decimal).

The emitter renders a snapshot's insert-only ColumnBatch natively (its keys
and its values written by native/hostops.cpp into one buffer each, the GIL
released: a MessageBlock the Kafka sink frames as it is), the same batch
in Python where the library is switched off
or declines, as it does replication's inserts with their per-row lsn, commit
time and txId, and anything else - updates, deletes, packers - row by row;
emitter.py's docstring has the rules.
"""

from transferia_tpu.debezium.emitter import DebeziumEmitter
from transferia_tpu.debezium.receiver import DebeziumReceiver

__all__ = ["DebeziumEmitter", "DebeziumReceiver"]
