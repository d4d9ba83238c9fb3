"""Plain references: straightforward, float32/float64, no code of the
package under test. A configuration names its model's file here."""
