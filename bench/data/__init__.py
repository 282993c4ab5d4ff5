"""Generators: one module per dataset, named by a configuration's
`dataset` key.  Each makes its tables from the seed in bulk, loads them
into the system under test, and keeps what its plain reference needs."""
