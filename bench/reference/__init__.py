"""Plain references: the deployment, a packet-level simulation and a fluid
model, written from the configuration alone."""
