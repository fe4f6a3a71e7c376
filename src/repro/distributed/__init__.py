"""HLO analysis of compiled programs (collectives, while trip counts)."""
